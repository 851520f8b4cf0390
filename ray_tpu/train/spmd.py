"""SPMD training step: jit-compiled, mesh-sharded, donated.

This is the data plane of the JaxTrainer equivalent (reference:
`python/ray/train/v2/jax/jax_trainer.py` — which only *orchestrates*; the
actual math lived in user code). Here the framework owns an optimized train
step: params/opt-state sharded per logical rules, batch split over (dp, fsdp),
buffers donated so XLA updates weights in place, gradient allreduce riding ICI.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.util import tracing
from ray_tpu.utils.platform import watch_compiles

P = PartitionSpec


@dataclasses.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any

    def tree_flatten(self):  # pragma: no cover - pytree protocol
        return (self.step, self.params, self.opt_state), None

    @classmethod
    def tree_unflatten(cls, aux, children):  # pragma: no cover
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten)


def default_optimizer(lr: float = 3e-4, weight_decay: float = 0.1,
                      warmup: int = 100, total_steps: int = 10_000,
                      b2: float = 0.95, clip: float = 1.0) -> optax.GradientTransformation:
    sched = optax.warmup_cosine_decay_schedule(0.0, lr, warmup, max(total_steps, warmup + 1))
    return optax.chain(
        optax.clip_by_global_norm(clip),
        optax.adamw(sched, b1=0.9, b2=b2, weight_decay=weight_decay),
    )


def _apply_optimizer(optimizer, state: "TrainState", grads):
    """(new params, new optimizer state, gradient norm), under the
    `optimizer` scope of the step program's operation names."""
    with jax.named_scope("optimizer"):
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params)
        return (optax.apply_updates(state.params, updates), opt_state,
                optax.global_norm(grads))


def _with_aux(loss_fn):
    """`loss_fn(params, batch)` may return the loss alone or `(loss, aux)`,
    `aux` a dict of scalars the job watches (a MoE's router losses and
    load). Either way: `(loss, aux)`, for `value_and_grad(has_aux=True)`."""
    def wrapped(params, batch):
        out = loss_fn(params, batch)
        return out if isinstance(out, tuple) else (out, {})
    return wrapped


def _loss_only(loss_fn):
    with_aux = _with_aux(loss_fn)
    return lambda params, batch: with_aux(params, batch)[0]


def state_shardings(state_shape: Any, params_spec: Any, mesh: Mesh) -> Any:
    """Shard params by spec; shard opt-state subtrees that mirror the param
    tree (adam mu/nu etc., matched by tree STRUCTURE, not leaf shape — two
    same-shaped params may have different specs); replicate everything else."""
    params_treedef = jax.tree.structure(state_shape.params)
    spec_leaves = [NamedSharding(mesh, s) for s in jax.tree.leaves(
        params_spec, is_leaf=lambda x: isinstance(x, PartitionSpec))]
    param_shardings = jax.tree.unflatten(params_treedef, spec_leaves)
    rep = NamedSharding(mesh, P())

    def assign(node):
        try:
            if jax.tree.structure(node) == params_treedef:
                return param_shardings
        except Exception:
            pass
        if isinstance(node, tuple):  # includes optax NamedTuple states
            vals = [assign(c) for c in node]
            return type(node)(*vals) if hasattr(node, "_fields") else tuple(vals)
        if isinstance(node, list):
            return [assign(c) for c in node]
        if isinstance(node, dict):
            return {k: assign(v) for k, v in node.items()}
        return rep

    return TrainState(
        step=rep,
        params=param_shardings,
        opt_state=assign(state_shape.opt_state),
    )


@dataclasses.dataclass
class CompiledTrain:
    """A fully-compiled SPMD training program bound to a mesh."""
    mesh: Mesh
    init_fn: Callable[[jax.Array], TrainState]        # key -> sharded TrainState
    step_fn: Callable[[TrainState, Any], tuple]       # (state, batch) -> (state, metrics)
    batch_sharding: Any
    state_sharding: Any
    # split step for cross-worker DDP: grads leave the jit boundary so the
    # gang can average them host-side (cross_worker_grad_sync) between the
    # two calls; in-mesh training uses the fused step_fn
    grad_fn: Optional[Callable[[TrainState, Any], tuple]] = None
    apply_fn: Optional[Callable[[TrainState, Any], TrainState]] = None
    # hierarchical (dp_inter, dp_intra) mesh extras: the Topology the dp
    # sub-axes express; the standalone jitted sync (state, batch) ->
    # (mean loss, averaged grads) for parity tests and benches; and — when
    # grad_quantize carries error feedback — the residual's sharding plus
    # a jitted zero-initializer, because the residual is STEP-FN STATE:
    # step_fn becomes (state, batch, ef) -> (state, metrics, ef)
    topology: Optional[Any] = None
    grad_quantize: Optional[Any] = None
    sync_fn: Optional[Callable[[TrainState, Any], tuple]] = None
    ef_sharding: Optional[Any] = None
    init_ef_fn: Optional[Callable[[], jax.Array]] = None
    # diagnostics window (compile_train(phase_timing=True)): the step split
    # into separately-timed phase programs — (state, batch) ->
    # (state, metrics) where metrics["phases"] maps
    # compute/rs/ar/ag/apply -> seconds. Trades the fused step's
    # single-program schedule for per-fabric attribution; not for
    # steady-state training.
    timed_step_fn: Optional[Callable[[TrainState, Any], tuple]] = None


def _expand_dp_spec(spec: PartitionSpec) -> PartitionSpec:
    """Rewrite `dp` in a PartitionSpec to the (dp_inter, dp_intra) pair."""
    parts = []
    for p in spec:
        if p == "dp":
            parts.append(mesh_lib.DP_SUB_AXES)
        elif isinstance(p, (tuple, list)) and "dp" in p:
            q: list = []
            for a in p:
                q.extend(mesh_lib.DP_SUB_AXES if a == "dp" else (a,))
            parts.append(tuple(q))
        else:
            parts.append(p)
    return P(*parts)


def _fused_hier_sync(loss_fn, mesh: Mesh, topo, params_spec, batch_spec,
                     n_grads: int, n_pad: int, quantize):
    """Build the in-program two-level gradient sync for a hierarchical
    (dp_inter, dp_intra) mesh: a closure (params, batch, step[, resid])
    -> (mean loss, averaged grads[, new resid]) whose dp reduction is
    EMITTED BY US inside a shard_map manual over the dp sub-axes —
    reduce-scatter over dp_intra, allreduce (optionally quantized) over
    dp_inter on the scattered shard only, all-gather back — so the
    compiled step never lowers a flat-world dp all-reduce and the slow
    fabric carries 1/intra of the gradient bytes (int8/fp8-width with
    `quantize`). Zero Python in the loop: the whole schedule is one XLA
    program.

    The local loss scalar reduces through two chained single-axis psums
    (dp_intra, then dp_inter) — same association as the vector schedule,
    never a flat-world group, and no 8 MB-scale concatenate/pad copy just
    to carry 4 bytes.
    """
    from jax.flatten_util import ravel_pytree

    from ray_tpu.util.collective.hierarchy import hier_grad_sync_program
    from jax import shard_map

    inter_ax, intra_ax = topo.inter_axis, topo.intra_axis
    world = topo.world
    ef = bool(quantize is not None and quantize.error_feedback)
    sr = bool(quantize is not None and quantize.stochastic_rounding)
    sync = hier_grad_sync_program(topo, quantize, error_feedback=ef)
    # Manual over ALL axes when dp is the only real parallelism (specs
    # pass through verbatim); otherwise manual over the dp pair only,
    # leaving fsdp/tp/... to the auto partitioner.
    other = [a for a in mesh.axis_names if a not in (inter_ax, intra_ax)]
    full_manual = all(int(mesh.shape[a]) == 1 for a in other)

    def body(p_l, b_l, ids_l, step_l, *rest):
        with mesh_lib.suppress_constraints():
            loss, grads = jax.value_and_grad(loss_fn)(p_l, b_l)
        flat, unravel = ravel_pytree(grads)
        vec = flat.astype(jnp.float32)
        if n_pad > vec.shape[0]:
            vec = jnp.pad(vec, (0, n_pad - vec.shape[0]))
        # rank arrives as a sharded iota operand: each shard reads its own
        # (inter, intra) index as ids_l[0, 0]
        key = (jax.random.fold_in(jax.random.PRNGKey(step_l), ids_l[0, 0])
               if sr else None)
        if ef:
            synced, new_r = sync(vec, rest[0][0, 0], key=key)
        else:
            synced = sync(vec, key=key)
        synced = synced / world
        loss_mean = jax.lax.psum(
            jax.lax.psum(loss.astype(jnp.float32), intra_ax),
            inter_ax) / world
        out_grads = jax.tree.map(lambda g, s: s.astype(g.dtype), grads,
                                 unravel(synced[:n_grads]))
        if ef:
            return loss_mean, out_grads, new_r[None, None]
        return loss_mean, out_grads

    is_spec = lambda x: isinstance(x, PartitionSpec)
    kw: dict = {"check_vma": False}
    if full_manual:
        p_in, b_in, g_out = params_spec, batch_spec, params_spec
    else:
        kw["axis_names"] = {inter_ax, intra_ax}
        p_in = jax.tree.map(lambda s: P(), params_spec, is_leaf=is_spec)
        g_out = p_in
        parts = []
        for p in batch_spec:  # keep only the manual (dp) axes of the spec
            names = p if isinstance(p, (tuple, list)) else (p,)
            q = tuple(a for a in names if a in (inter_ax, intra_ax))
            parts.append(q if q else None)
        b_in = P(*parts)
    r_spec = P(inter_ax, intra_ax)
    in_specs = (p_in, b_in, r_spec, P()) + ((r_spec,) if ef else ())
    out_specs = (P(), g_out) + ((r_spec,) if ef else ())
    sm = shard_map(body, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, **kw)

    def _sync_call(params, batch, step, resid=None):
        ids = jnp.arange(world, dtype=jnp.int32).reshape(
            topo.inter, topo.intra)
        args = (params, batch, ids, step) + ((resid,) if ef else ())
        return sm(*args)

    return _sync_call


_phase_hist = None


def _publish_phase_stats(run: str, rank: int, phases: dict) -> None:
    """Per-phase step-time telemetry from the timed diagnostics step:
    a `train_step_phase_seconds{phase}` histogram for /metrics plus a
    per-rank `train_phase` workload row the head merges and the
    workload watchdog scans for rank stragglers (one rank's step_s far
    above the gang median). Rides the existing metrics push — no new
    RPCs. Best-effort: a process without metrics wiring times fine."""
    global _phase_hist
    try:
        from ray_tpu.util import metrics as m

        if _phase_hist is None:
            _phase_hist = m.Histogram(
                "train_step_phase_seconds",
                "Fused-step time attributed per phase by the timed "
                "diagnostics step (compute=fwd+bwd, rs/ag=intra fabric, "
                "ar=inter fabric, apply=optimizer)",
                boundaries=[0.001, 0.005, 0.02, 0.1, 0.5, 2.0, 10.0],
                tag_keys=("phase",))
        for ph, dt in phases.items():
            _phase_hist.observe(dt, tags={"phase": ph})
        row = {"rank": rank, "step_s": round(sum(phases.values()), 6)}
        row.update({f"{k}_s": round(v, 6) for k, v in phases.items()})
        m.publish_workload("train_phase", f"{run}:{rank}", row)
    except Exception:
        pass


def _timed_hier_step(loss_fn, mesh: Mesh, topo, params_spec, batch_spec,
                     state_shape, state_sharding, batch_sharding,
                     optimizer, rules, n_grads: int, n_pad: int, quantize):
    """Build the diagnostics-window timed step for a hierarchical mesh:
    the fused schedule re-expressed as FIVE separate programs — grad
    (fwd+bwd, no dp reduction), RS(dp_intra), AR(dp_inter), AG(dp_intra),
    optimizer apply — each timed host-side with block_until_ready, so a
    step's wall time decomposes onto the fabric that spent it. The phase
    bodies come from `hierarchy.hier_phase_programs`; the specs mirror
    `_fused_hier_sync` so the lowering per phase is the same collective
    the fused step would have emitted, just unfused."""
    from jax.flatten_util import ravel_pytree

    from ray_tpu.util.collective.hierarchy import hier_phase_programs
    from jax import shard_map

    inter_ax, intra_ax = topo.inter_axis, topo.intra_axis
    world = topo.world
    bodies = hier_phase_programs(topo, quantize)
    other = [a for a in mesh.axis_names if a not in (inter_ax, intra_ax)]
    full_manual = all(int(mesh.shape[a]) == 1 for a in other)

    def grad_body(p_l, b_l):
        with mesh_lib.suppress_constraints():
            loss, grads = jax.value_and_grad(loss_fn)(p_l, b_l)
        flat, _ = ravel_pytree(grads)
        vec = flat.astype(jnp.float32)
        if n_pad > vec.shape[0]:
            vec = jnp.pad(vec, (0, n_pad - vec.shape[0]))
        return loss.astype(jnp.float32)[None, None], vec[None, None]

    is_spec = lambda x: isinstance(x, PartitionSpec)
    kw: dict = {"check_vma": False}
    if full_manual:
        p_in, b_in = params_spec, batch_spec
    else:
        kw["axis_names"] = {inter_ax, intra_ax}
        p_in = jax.tree.map(lambda s: P(), params_spec, is_leaf=is_spec)
        parts = []
        for p in batch_spec:
            names = p if isinstance(p, (tuple, list)) else (p,)
            q = tuple(a for a in names if a in (inter_ax, intra_ax))
            parts.append(q if q else None)
        b_in = P(*parts)
    r_spec = P(inter_ax, intra_ax)
    grad_prog = jax.jit(shard_map(
        grad_body, mesh=mesh, in_specs=(p_in, b_in),
        out_specs=(r_spec, r_spec), **kw))
    rs_prog = jax.jit(shard_map(
        lambda v: bodies["rs"](v[0, 0])[None, None], mesh=mesh,
        in_specs=(r_spec,), out_specs=r_spec, **kw))
    ar_prog = jax.jit(shard_map(
        lambda s: bodies["ar"](s[0, 0])[None, None], mesh=mesh,
        in_specs=(r_spec,), out_specs=r_spec, **kw))
    # after AR(inter)+AG(intra) every device holds the identical synced
    # vector: out_spec P() hands it back replicated
    ag_prog = jax.jit(shard_map(
        lambda s: bodies["ag"](s[0, 0]), mesh=mesh,
        in_specs=(r_spec,), out_specs=P(), **kw))

    # unravel built from a concrete f32 zero tree (eval_shape leaves are
    # abstract); the apply program casts back to each param's dtype
    zeros = jax.tree.map(lambda l: jnp.zeros(l.shape, jnp.float32),
                         jax.tree.leaves(state_shape.params))
    treedef = jax.tree.structure(state_shape.params)
    _, unravel = ravel_pytree(jax.tree.unflatten(treedef, zeros))

    def _apply(state: TrainState, synced):
        with mesh_lib.use_mesh(mesh, rules):
            grads = jax.tree.map(
                lambda t, g: g.astype(t.dtype), state.params,
                unravel(synced[:n_grads] / world))
            params, opt_state, grad_norm = _apply_optimizer(
                optimizer, state, grads)
            return (TrainState(state.step + 1, params, opt_state),
                    grad_norm)

    rep = NamedSharding(mesh, P())
    apply_prog = jax.jit(
        _apply, in_shardings=(state_sharding, rep),
        out_shardings=(state_sharding, rep), donate_argnums=(0,))

    def timed_step(state: TrainState, batch, *, rank: int = 0,
                   run: str = "train", publish: bool = True):
        import time as _time

        phases = {}

        def _timed(name, fn, *a):
            t0 = _time.perf_counter()
            out = fn(*a)
            jax.block_until_ready(out)
            phases[name] = _time.perf_counter() - t0
            return out

        with mesh_lib.use_mesh(mesh, rules):
            loss, vec = _timed("compute", grad_prog, state.params, batch)
            shard = _timed("rs", rs_prog, vec)
            red = _timed("ar", ar_prog, shard)
            synced = _timed("ag", ag_prog, red)
            (state, grad_norm) = _timed("apply", apply_prog, state, synced)
        if publish:
            _publish_phase_stats(run, rank, phases)
        metrics = {"loss": float(np.mean(jax.device_get(loss))),
                   "grad_norm": grad_norm, "step": state.step,
                   "phases": phases}
        return state, metrics

    return timed_step


def _record_loop_prelude() -> None:
    """In a trainer worker's loop, the start-up span `train.loop_prelude`:
    the loop's first line -> its first `compile_train`. The user's own
    code, and where a loop first touches JAX: the devices open in it."""
    from ray_tpu.train import session

    ctx = getattr(session._ctx, "value", None)
    since = getattr(ctx, "loop_start_ts", None)
    if since is not None:
        ctx.loop_start_ts = None
        tracing.record_startup("train.loop_prelude", since, time.time(),
                               rank=ctx.rank)


def _timed_first_call(init_fn):
    """`init_fn`, its first real call inside the start-up span
    `train.init_state`: the program prepared and the state made on the
    devices (the call waits for it, as its caller is about to). A trace
    of it (`jax.eval_shape`) passes through."""
    pending = [True]

    def init(key):
        if not pending or isinstance(key, jax.core.Tracer):
            return init_fn(key)
        pending.clear()
        with tracing.startup_span("train.init_state"):
            return jax.block_until_ready(init_fn(key))

    return init


@tracing.startup_span("train.compile")
def compile_train(
    loss_fn: Callable[[Any, Any], jax.Array],
    init_params_fn: Callable[[jax.Array], Any],
    params_spec: Any,
    mesh: Mesh,
    optimizer: Optional[optax.GradientTransformation] = None,
    batch_spec: Optional[PartitionSpec] = None,
    rules: Optional[dict] = None,
    grad_quantize: Optional[Any] = None,
    phase_timing: bool = False,
) -> CompiledTrain:
    """Build sharded init + train-step functions for an arbitrary model.

    loss_fn(params, batch) -> scalar, or (scalar, aux) with `aux` a dict of
    scalars that joins the fused step's `metrics` beside `loss`,
    `grad_norm` and `step` (the hierarchical-mesh and split grad/apply
    programs take the loss alone); init_params_fn(key) -> params pytree;
    params_spec: PartitionSpec pytree matching params.

    On a hierarchical mesh (`mesh_lib.build_hierarchical_mesh`, dp split
    into `(dp_inter, dp_intra)`) the fused `step_fn` emits the two-level
    gradient sync in-program (see `_fused_hier_sync`), optionally with a
    quantized inter hop (`grad_quantize=QuantizedAllreduce(...)`). With
    error feedback the quantization residual is step-fn state:
    `step_fn(state, batch, ef) -> (state, metrics, ef)`, seeded by
    `init_ef_fn()`. `batch_spec=None` picks the mesh's dp spelling.

    `phase_timing=True` (hierarchical mesh only) additionally builds
    `timed_step_fn`: the same schedule split into separately-timed
    programs (compute/RS/AR/AG/apply) publishing
    `train_step_phase_seconds{phase}` and per-rank `train_phase`
    workload rows — an opt-in diagnostics window, not a replacement for
    the fused `step_fn`.
    """
    watch_compiles()
    _record_loop_prelude()
    optimizer = optimizer or default_optimizer()
    loss_aux_fn, loss_fn = _with_aux(loss_fn), _loss_only(loss_fn)
    hier = mesh_lib.is_hierarchical_mesh(mesh)
    if batch_spec is None:
        batch_spec = (P((*mesh_lib.DP_SUB_AXES, "fsdp")) if hier
                      else P(("dp", "fsdp")))
    elif hier:
        batch_spec = _expand_dp_spec(batch_spec)
    if hier:
        rules = mesh_lib.rules_for_mesh(mesh, rules)
    elif grad_quantize is not None:
        raise ValueError(
            "grad_quantize runs on the inter hop of a hierarchical mesh; "
            "build one with mesh.build_hierarchical_mesh")
    batch_sharding = NamedSharding(mesh, batch_spec)
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), params_spec,
                           is_leaf=lambda x: isinstance(x, PartitionSpec))

    def _init(key):
        params = init_params_fn(key)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=optimizer.init(params))

    state_shape = jax.eval_shape(_init, jax.random.key(0))
    state_sharding = state_shardings(state_shape, params_spec, mesh)

    init_fn = jax.jit(_init, out_shardings=state_sharding)

    rep = NamedSharding(mesh, P())
    topo = mesh_lib.hier_topology(mesh) if hier else None
    ef = bool(hier and grad_quantize is not None
              and grad_quantize.error_feedback)
    sync_fn = ef_sharding = init_ef_fn = timed_step_fn = None
    if phase_timing and not hier:
        raise ValueError(
            "phase_timing splits the two-level gradient sync into timed "
            "phases; build a hierarchical mesh "
            "(mesh.build_hierarchical_mesh) to use it")
    if phase_timing and ef:
        raise ValueError(
            "phase_timing does not support error-feedback quantization "
            "(the residual is fused-step state)")

    if hier:
        # Pad the fused grad vector so the intra scatter tiles evenly
        # and (when quantized) each shard is whole scale-chunks; aligned
        # models (n_grads % (intra*chunk) == 0) pad nothing.
        n_grads = sum(int(np.prod(l.shape)) for l in
                      jax.tree.leaves(state_shape.params))
        per_shard = -(-n_grads // topo.intra)
        if grad_quantize is not None:
            per_shard = grad_quantize.padded_size(per_shard)
        n_pad = per_shard * topo.intra
        fused_sync = _fused_hier_sync(
            loss_fn, mesh, topo, params_spec, batch_spec,
            n_grads, n_pad, grad_quantize)
        ef_shape = (topo.inter, topo.intra, per_shard)
        ef_sharding = NamedSharding(
            mesh, P(topo.inter_axis, topo.intra_axis))

        def _step(state: TrainState, batch, *ef_args):
            with mesh_lib.use_mesh(mesh, rules):
                if ef:
                    loss, grads, new_ef = fused_sync(
                        state.params, batch, state.step, ef_args[0])
                else:
                    loss, grads = fused_sync(state.params, batch,
                                             state.step)
                params, opt_state, grad_norm = _apply_optimizer(
                    optimizer, state, grads)
                metrics = {
                    "loss": loss,
                    "grad_norm": grad_norm,
                    "step": state.step + 1,
                }
                out = TrainState(state.step + 1, params, opt_state)
                return (out, metrics, new_ef) if ef else (out, metrics)

        step_fn = jax.jit(
            _step,
            in_shardings=(state_sharding, batch_sharding)
            + ((ef_sharding,) if ef else ()),
            out_shardings=(state_sharding, rep)
            + ((ef_sharding,) if ef else ()),
            donate_argnums=(0, 2) if ef else (0,),
        )

        if ef:
            init_ef_fn = jax.jit(
                lambda: jnp.zeros(ef_shape, jnp.float32),
                out_shardings=ef_sharding)

        def _sync_only(state: TrainState, batch):
            with mesh_lib.use_mesh(mesh, rules):
                out = fused_sync(
                    state.params, batch, state.step,
                    *((jnp.zeros(ef_shape, jnp.float32),) if ef else ()))
                return out[0], out[1]

        sync_fn = jax.jit(
            _sync_only,
            in_shardings=(state_sharding, batch_sharding),
            out_shardings=(rep, state_sharding.params))

        if phase_timing:
            timed_step_fn = _timed_hier_step(
                loss_fn, mesh, topo, params_spec, batch_spec,
                state_shape, state_sharding, batch_sharding,
                optimizer, rules, n_grads, n_pad, grad_quantize)
    else:
        def _step(state: TrainState, batch):
            with mesh_lib.use_mesh(mesh, rules):
                (loss, aux), grads = jax.value_and_grad(
                    loss_aux_fn, has_aux=True)(state.params, batch)
                params, opt_state, grad_norm = _apply_optimizer(
                    optimizer, state, grads)
                metrics = {
                    "loss": loss,
                    "grad_norm": grad_norm,
                    "step": state.step + 1,
                    **aux,
                }
                return TrainState(state.step + 1, params, opt_state), metrics

        step_fn = jax.jit(
            _step,
            in_shardings=(state_sharding, batch_sharding),
            out_shardings=(state_sharding, NamedSharding(mesh, P())),
            donate_argnums=(0,),
        )

    def _grads(state: TrainState, batch):
        with mesh_lib.use_mesh(mesh, rules):
            return jax.value_and_grad(loss_fn)(state.params, batch)

    grad_fn = jax.jit(
        _grads,
        in_shardings=(state_sharding, batch_sharding),
        out_shardings=(rep, state_sharding.params),
    )

    def _apply(state: TrainState, grads):
        with mesh_lib.use_mesh(mesh, rules):
            params, opt_state, _ = _apply_optimizer(optimizer, state, grads)
            return TrainState(state.step + 1, params, opt_state)

    apply_fn = jax.jit(
        _apply,
        in_shardings=(state_sharding, state_sharding.params),
        out_shardings=state_sharding,
        donate_argnums=(0,),
    )
    return CompiledTrain(mesh=mesh, init_fn=_timed_first_call(init_fn),
                         step_fn=step_fn,
                         batch_sharding=batch_sharding,
                         state_sharding=state_sharding,
                         grad_fn=grad_fn, apply_fn=apply_fn,
                         topology=topo, grad_quantize=grad_quantize,
                         sync_fn=sync_fn, ef_sharding=ef_sharding,
                         init_ef_fn=init_ef_fn, timed_step_fn=timed_step_fn)


# ---------------------------------------------------------------------------
# World-size-agnostic state checkpoints (elastic fault tolerance).
#
# save: every process writes the chunks it can address, with global index
# windows in the manifest (train/checkpoint.py save_sharded). restore:
# gather-on-restore assembles full arrays and device_puts them under the
# NEW mesh's shardings — a checkpoint saved at world size 4 restores at 2,
# 1, or back at 4, bitwise-identically after gather.
# ---------------------------------------------------------------------------

def _state_as_tree(state: TrainState) -> dict:
    # dict wrapper so manifest leaf keys are stable path strings
    # ("params/wte", "opt_state/1/0/mu/...") rather than flatten indices
    return {"step": state.step, "params": state.params,
            "opt_state": state.opt_state}


def save_state_sharded(state: TrainState, path: str, *,
                       world_size: int = 1, process_index: int = 0) -> str:
    from ray_tpu.train import checkpoint as ckpt_lib

    return ckpt_lib.save_sharded(
        _state_as_tree(state), path,
        step=int(jax.device_get(state.step)),
        world_size=world_size, process_index=process_index)


def restore_state_sharded(path: str, compiled: CompiledTrain, *,
                          stream_chunk_bytes: Optional[int] = None,
                          stream_in_flight: int = 2) -> TrainState:
    """Restore a `save_state_sharded` checkpoint onto `compiled`'s mesh.

    The target mesh may have a different shape / device count than the
    save-time mesh: arrays are gathered to global form on the host, then
    redistributed by `collective.reshard` under `compiled.state_sharding`
    — each destination device receives ONLY its own index window (one
    shard of device memory peak), not a full copy that XLA then slices.

    With `stream_chunk_bytes` set the restore STREAMS instead of
    gathering: each leaf is opened lazily (`checkpoint.open_sharded`)
    and redistributed chunk-at-a-time by
    `collective.reshard_streaming`, so peak host memory is
    ~`stream_in_flight * stream_chunk_bytes` per leaf rather than the
    model size — leaves larger than host memory restore fine.
    Bitwise-identical to the gathering path.
    """
    from ray_tpu.util.collective import (reshard as _reshard,
                                         reshard_streaming as _stream)
    from ray_tpu.train import checkpoint as ckpt_lib

    if stream_chunk_bytes is None:
        flat, _ = ckpt_lib.load_sharded(path)
    else:
        flat, _ = ckpt_lib.open_sharded(path)
    state_shape = jax.eval_shape(compiled.init_fn, jax.random.key(0))
    template = jax.tree_util.tree_flatten_with_path(
        _state_as_tree(state_shape))[0]
    shard_leaves = {ckpt_lib._leaf_key(kp): leaf for kp, leaf in
                    jax.tree_util.tree_flatten_with_path(
                        _state_as_tree(compiled.state_sharding),
                        is_leaf=lambda x: isinstance(x, NamedSharding))[0]}
    restored = []
    for kp, leaf in template:
        key = ckpt_lib._leaf_key(kp)
        if key not in flat:
            raise KeyError(f"checkpoint {path} has no leaf {key!r}")
        arr = flat[key]
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"leaf {key}: checkpoint shape {arr.shape} "
                             f"!= program shape {leaf.shape}")
        if stream_chunk_bytes is None:
            restored.append(_reshard(np.asarray(arr).astype(leaf.dtype),
                                     shard_leaves[key]))
        else:
            restored.append(_stream(arr, shard_leaves[key],
                                    chunk_bytes=stream_chunk_bytes,
                                    max_in_flight=stream_in_flight,
                                    out_dtype=leaf.dtype))
    treedef = jax.tree_util.tree_structure(_state_as_tree(state_shape))
    tree = jax.tree_util.tree_unflatten(treedef, restored)
    return TrainState(step=tree["step"], params=tree["params"],
                      opt_state=tree["opt_state"])


def cross_worker_grad_sync(grads: Any, group_name: str, world_size: int,
                           timeout: float = 60.0,
                           quantize: Optional[Any] = None) -> Any:
    """Average a gradient pytree across the worker gang (elastic DDP).

    XLA meshes allreduce in-program over ICI; ACROSS worker processes
    there are two planes. When the gang is an `xla-multihost` group the
    sync runs the DEVICE hierarchical path (`allreduce_tree`): one fused
    buffer, reduced over the gang's hosts x local-devices topology with
    the slow inter-host hop carrying only 1/intra of the bytes — and,
    with `quantize=QuantizedAllreduce(...)`, carrying it at int8/fp8
    width with error-feedback residuals. Gradient bytes ride the gang's
    own transport (ICI/DCN/gloo); the head KV carries nothing.

    The kv collective stays the CPU-only/CI fallback: one fused host
    allreduce per step so the rendezvous cost is O(1) per step, not
    O(n_leaves). No-op at world size 1. `group_name` should carry the
    group generation (e.g. "ddp:g3") so a rebuilt gang never collides
    with a fenced predecessor's rendezvous keys. `timeout` bounds only
    the kv fallback's rendezvous; the device path blocks until the gang
    completes (a dead member is detected and fenced by the elastic
    controller's death watch, not by a deadline here).
    """
    if world_size <= 1:
        return grads
    import numpy as np

    from ray_tpu.util import collective

    group = collective.get_group(group_name)
    if getattr(group, "backend_name", "") == "xla-multihost":
        return group.allreduce_tree(grads, average=True, quantize=quantize,
                                    timeout=timeout)
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    arrs = [np.asarray(leaf) for leaf in leaves]
    fused = np.concatenate([a.ravel().astype(np.float32) for a in arrs])
    group.allreduce(fused, timeout=timeout)
    fused /= world_size
    out, offset = [], 0
    for a, leaf in zip(arrs, leaves):
        out.append(jnp.asarray(
            fused[offset:offset + a.size].reshape(a.shape),
            dtype=leaf.dtype))
        offset += a.size
    return jax.tree_util.tree_unflatten(treedef, out)


def compile_model_train(model_mod, cfg, mesh: Mesh, optimizer=None,
                        rules=None) -> CompiledTrain:
    """compile_train for any model module exposing loss_fn/init_params/
    param_specs (ray_tpu.models.{gpt2,llama,moe})."""
    with mesh_lib.use_mesh(mesh, rules):
        spec = model_mod.param_specs(cfg)
    return compile_train(
        loss_fn=partial(model_mod.loss_fn, cfg=cfg),
        init_params_fn=partial(model_mod.init_params, cfg=cfg),
        params_spec=spec,
        mesh=mesh,
        optimizer=optimizer,
        rules=rules,
    )


def compile_gpt2_train(cfg, mesh: Mesh, optimizer=None, rules=None) -> CompiledTrain:
    from ray_tpu.models import gpt2

    return compile_model_train(gpt2, cfg, mesh, optimizer, rules)


def compile_pipeline_train(model_mod, cfg, mesh: Mesh, n_microbatches: int,
                           optimizer=None, rules=None) -> CompiledTrain:
    """Pipeline-parallel training: the block stack runs as a GPipe microbatch
    pipeline over the mesh's `pp` axis (ray_tpu.parallel.pipeline), embedding/
    unembed/loss stay ordinary pjit code. Works for models whose blocks are
    layer-stacked with a `_block(x, bp, cfg)` body (gpt2, llama).

    Under pp the stacked layer dim is sharded over `pp` (logical rule
    "layers" -> "pp") so each stage holds only its own layers' weights.
    """
    from ray_tpu.parallel.pipeline import (make_stage_fn, pipeline_apply,
                                           stack_stages)

    F = mesh.shape["pp"]
    if cfg.n_layer % max(F, 1):
        raise ValueError(f"n_layer={cfg.n_layer} not divisible by pp={F}")
    rules = {**(rules or {}), "layers": "pp"}
    with mesh_lib.use_mesh(mesh, rules):
        spec = model_mod.param_specs(cfg)

    stage_fn = make_stage_fn(lambda x, bp: model_mod._block(x, bp, cfg),
                             remat=cfg.remat)

    from ray_tpu.models.lm import cross_entropy, split_lm_batch

    def loss_fn(params, batch):
        inputs, targets = split_lm_batch(batch)
        x = model_mod.embed(params, inputs, cfg)
        stage_params = stack_stages(params["blocks"], F)
        x = pipeline_apply(stage_fn, stage_params, x,
                           n_microbatches=n_microbatches, mesh=mesh)
        return cross_entropy(model_mod.unembed(params, x, cfg), targets)

    return compile_train(
        loss_fn=loss_fn,
        init_params_fn=partial(model_mod.init_params, cfg=cfg),
        params_spec=spec,
        mesh=mesh,
        optimizer=optimizer,
        rules=rules,
    )
