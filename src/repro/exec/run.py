"""Execute a :class:`NetworkPlan` — the single dispatch path behind
`mapped_net_apply`, `train_cnn(executor=...)`, and `serve_cnn`.

`execute_plan` runs the whole forward as **one jitted XLA program**: the
plan (frozen, hashable) is a static argument, so the per-layer Python
loops — super-steps, placement groups, glue — unroll at trace time and
the runtime sees a single launch per forward instead of one per layer.
Cross-layer overlap is *bounded, one layer deep*: each layer boundary
threads the carry and the still-unconsumed kernels through
`lax.optimization_barrier`, leaving exactly the next layer's
kernel-side work (its shifted-weight-matrix blocks, its patch-gather
indices) free to issue while this layer's cross-row `psum` drains.
Without the barrier XLA hoists EVERY layer's kernel-derived tensors to
the program start — all shifted weight matrices live at once — which
measurably loses to the per-layer loop on deep concat stacks
(benchmarks/plan_bench.py tracks both).  That in-program build runs only
when the kernels are traced (or numpy): with fixed kernels the mapped
layers read the network's prepared blocks as program inputs instead
(exec/constants.py), built once per kernel set and shared by every
tier.  Inter-layer carry buffers live
inside the program (reused/donated by the compiler rather than
round-tripping through host dispatch); Python-loop dispatch survives
only *between* forwards — within one, nothing serializes on the host.

`execute_looped` keeps the pre-plan behavior — one jit launch per layer
with eager glue between — as the measurement baseline for
benchmarks/plan_bench.py's dispatch-count and wall-clock comparison.

`apply_layer` dispatches ONE layer of a (possibly layerwise) plan
through the per-executor jit entries — the `cnn/models.apply_cnn` path,
which owns its own pooling/bias plumbing between convs.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.cnn.cim_conv import cim_conv2d_jit, cim_conv2d_traced
from repro.cnn.mapped_net import mapped_conv2d_jit, mapped_conv2d_traced
from repro.launch.device import compile_stats
from repro.launch.tracing import GLUE, span
from .constants import _prepare_blocks, fixed_constants, note_call
from .glue import (ACTIVATIONS, attention_stage, center_crop, fit_spatial,
                   layernorm, stage_pool)
from .plan import LayerPlan, NetworkPlan, mesh_axes


def _layer_conv(lp: LayerPlan, x: jnp.ndarray, kernel: jnp.ndarray,
                mesh, *, jitted: bool, prepared=None) -> jnp.ndarray:
    """Dispatch one layer to its planned executor — traced bodies when
    inlining into the whole-forward program, jit entries when launched
    stand-alone (`execute_looped` / `apply_layer`).  ``prepared`` is the
    layer's pre-materialized shifted-weight blocks
    (exec/constants.PlanConstants), consumed by the mapped executor in
    place of the in-trace build."""
    m = lp.mapping
    mesh = mesh if lp.use_mesh else None
    if lp.executor == "mapped":
        fn = mapped_conv2d_jit if jitted else mapped_conv2d_traced
        return fn(m, x, kernel, mesh=mesh, weights=prepared)
    if lp.executor == "matmul":
        from repro.kernels.matmul_exec import (matmul_layer_jit,
                                               matmul_layer_traced)
        fn = matmul_layer_jit if jitted else matmul_layer_traced
        return fn(m, x, kernel, interpret=lp.interpret)
    if lp.executor == "sdk":
        from repro.kernels.im2win_conv import sdk_conv_jit, sdk_conv_traced
        fn = sdk_conv_jit if jitted else sdk_conv_traced
        return fn(m, x, kernel, interpret=lp.interpret, block=lp.block,
                  vmem_budget=lp.vmem_budget)
    fn = cim_conv2d_jit if jitted else cim_conv2d_traced
    return fn(m, x, kernel)


#: Fused-forward trace counter: `_forward` with ``jitted=False`` runs
#: only while `_execute_jit` / `_execute_jit_donated` traces (jit caches
#: replays), so this counts whole-program recompiles — the lookahead
#: regression test asserts exactly one per distinct plan.lookahead.
#: Diagnostics only; reset freely in tests.
fused_trace_count: int = 0


@jax.custom_jvp
def _fence(operands):
    """`lax.optimization_barrier` with a differentiation rule: the fence
    shapes the forward schedule only, so its tangent/cotangent is the
    identity (this jax version implements no rule of its own)."""
    return lax.optimization_barrier(operands)


@_fence.defjvp
def _fence_jvp(primals, tangents):
    (operands,), (dots,) = primals, tangents
    return _fence(operands), dots


def _forward(plan: NetworkPlan, kernels, x: jnp.ndarray, mesh,
             activation, *, jitted: bool, conv=None,
             consts=None) -> jnp.ndarray:
    """The planned forward chain.  Glue kinds were classified at compile
    time (exec/glue.py); this only replays them.  ``conv`` overrides the
    per-layer executor (the lax.conv oracle of `execute_oracle`).
    ``consts`` is PlanConstants.weights — per-layer pre-materialized
    shifted-weight blocks.  Deliberately NOT threaded through the
    lookahead fence below: the fence bounds *in-program* kernel-side
    prep, and a pre-materialized buffer has none — XLA hoisting a plain
    program input to the start is free.

    Each layer runs in its named scope ``L{i:02d}.{name}``; what lies
    outside its executor runs under ``glue`` (a ``pool`` stage and a
    module's ``join`` in sub-scopes of those names), the executor's own
    work under its phases (launch/tracing.PHASES)."""
    lay0 = plan.layers[0].mapping.layer
    if x.shape[1] != lay0.ic:
        raise ValueError(f"{lay0.name}: input has {x.shape[1]} channels,"
                         f" layer expects {lay0.ic}")
    fused = not jitted and conv is None     # one program: fence hoisting
    if fused:
        global fused_trace_count
        fused_trace_count += 1
    # with explicit glue (transformer lowerings) the glue owns every
    # nonlinearity — the network-global activation applies only to
    # inferred-glue (CNN) plans, where no GlueSpec.act is ever set
    explicit = plan.net.glue is not None

    def _segment(s, e, x, seg_kernels, seg_consts):
        """Layers [s, e) on carry ``x`` — the whole net in one call for
        unsegmented plans, one `jax.checkpoint` body per plan segment
        otherwise.  The saved stack and a module's open branches are
        segment-local: the segment pass only cuts where both are empty
        (exec/remat.py)."""
        seg_kernels = list(seg_kernels)
        saved = []                  # GlueSpec.save stack (residual bases,
        #                           # module inputs)
        branches = []               # outputs of the open module's branches
        for i in range(s, e):
            lp = plan.layers[i]
            lay = lp.mapping.layer
            spec = lp.glue
            with jax.named_scope(f"L{i:02d}.{lay.name}"):
                with jax.named_scope(GLUE):
                    if spec.pool != "none":
                        with jax.named_scope("pool"):
                            x = stage_pool(x, spec.pool)
                    xp = fit_spatial(x, lay.i_h, lay.i_w)
                    if spec.save:   # residual base: the pre-norm input
                        saved.append(xp)
                    xin = layernorm(xp) if spec.pre == "layernorm" else xp
                y = conv(lp, xin, seg_kernels[i - s]) if conv is not None \
                    else _layer_conv(
                        lp, xin, seg_kernels[i - s], mesh, jitted=jitted,
                        prepared=None if seg_consts is None
                        else seg_consts[i - s])
                with jax.named_scope(GLUE):
                    if spec.act != "none":
                        y = ACTIVATIONS[spec.act](y)
                    elif activation is not None and not explicit:
                        y = activation(y)
                    if spec.post == "attention":
                        # the opaque stage between mapped qkv and o
                        # projections — glue, not a mapped layer, so
                        # cycle accounting is untouched
                        y = attention_stage(y, spec.heads, spec.causal,
                                            interpret=lp.interpret)
                    if spec.kind == "concat":
                        skip = center_crop(xp, y.shape[-2], y.shape[-1])
                        x = jnp.concatenate([skip, y], axis=1)
                    elif spec.kind == "residual":
                        # channel match was validated at compile time;
                        # saved bases are deliberately NOT threaded
                        # through the lookahead fence — they are live
                        # carries, not kernel-side prep
                        x = saved.pop() + y
                    elif spec.kind == "join":
                        # branch order and widths were checked at
                        # compile time; the module input is done with
                        with jax.named_scope("join"):
                            x = jnp.concatenate(branches + [y], axis=1)
                        branches = []
                        saved.pop()
                    elif spec.kind == "branch":
                        # the module's next branch reads its input
                        branches.append(y)
                        x = saved[-1]
                    else:           # "chain" / "last"
                        x = y
                    # cross-layer pipeline depth (plan.lookahead, a
                    # compile_plan argument): kernels of layers beyond
                    # ``i + 1 + lookahead`` stay fenced behind this
                    # carry, so that many layers of kernel-side prep
                    # (weight-matrix blocks, gather indices) may overlap
                    # the current psum drain while the live working set
                    # stays bounded.  The window is clamped to the
                    # segment (``j < e``): pipelining never reaches
                    # across a checkpoint boundary
                    j = i + 1 + plan.lookahead
                    if fused and j < e:
                        # bounded pipelining (module docstring): layers
                        # past the lookahead window cannot start until
                        # this carry exists
                        x, *rest = _fence((x, *seg_kernels[j - s:]))
                        seg_kernels[j - s:] = rest
        return x

    kernels = list(kernels)
    consts_l = None if consts is None else list(consts)
    spans = plan.spans
    if not fused or len(spans) == 1:
        # unsegmented (or per-layer/oracle dispatch, which never
        # checkpoints): the PR-4 program shape, bit for bit
        return _segment(0, len(plan.layers), x, kernels, consts_l)
    for s, e in spans:
        seg_c = None if consts_l is None else tuple(consts_l[s:e])
        # jax.checkpoint per segment: the backward re-runs the segment
        # from its boundary carry instead of saving every layer's
        # residuals — the memory model DESIGN.md §13 prices
        x = jax.checkpoint(functools.partial(_segment, s, e))(
            x, tuple(kernels[s:e]), seg_c)
        j = e + plan.lookahead
        if j < len(plan.layers):
            # the boundary acts as the fence for later segments: their
            # kernel-side prep (beyond the lookahead window) waits on
            # the carry crossing the boundary, exactly as it would have
            # at a plain layer boundary
            x, *rest = _fence((x, *kernels[j:]))
            kernels[j:] = rest
    return x


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("mesh", "activation"))
def _execute_jit(plan, kernels, x, consts=None, *, mesh=None,
                 activation=None):
    return _forward(plan, kernels, x, mesh, activation, jitted=False,
                    consts=consts)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,),
                   static_argnames=("mesh", "activation"))
def _execute_jit_donated(plan, kernels, x, consts=None, *, mesh=None,
                        activation=None):
    return _forward(plan, kernels, x, mesh, activation, jitted=False,
                    consts=consts)


def donation_supported(mesh=None) -> bool:
    """Whether XLA implements input-buffer donation where the plan will
    actually run.  With a mesh bound, that is the mesh's device platform
    — which may differ from ``jax.default_backend()`` (forced host
    meshes, a CPU mesh next to an accelerator) — else the default
    backend.  CPU (and mixed-platform meshes) never donate; callers fall
    back cleanly to the non-donating entry."""
    from repro.launch.mesh import mesh_platform
    platform = mesh_platform(mesh)
    if platform is None:
        platform = jax.default_backend()
    return platform not in ("cpu", "mixed")


def _check_call(plan: NetworkPlan, kernels, x, mesh) -> None:
    if not plan.chained:
        raise ValueError(
            "execute_plan needs a chained plan; this one was compiled "
            "with chained=False (per-layer dispatch via apply_layer)")
    if len(kernels) != len(plan.layers):
        raise ValueError(f"{len(kernels)} kernels for "
                         f"{len(plan.layers)} planned layers")
    axes = mesh_axes(mesh)
    if axes != plan.mesh_axes:
        raise ValueError(
            f"mesh {axes} does not match the plan's compile mesh "
            f"{plan.mesh_axes} — recompile the plan for this mesh")
    if plan.batch is not None and x.shape[0] != plan.batch:
        raise ValueError(
            f"batch {x.shape[0]} != plan batch {plan.batch} — pad the "
            f"request (launch/serve_cnn pad-and-mask) or recompile")
    lay0 = plan.layers[0].mapping.layer
    if x.shape[1] != lay0.ic:
        raise ValueError(f"{lay0.name}: input has {x.shape[1]} channels,"
                         f" layer expects {lay0.ic}")


def execute_plan(plan: NetworkPlan, kernels: Sequence[jnp.ndarray],
                 x: jnp.ndarray, *, mesh=None, activation=None,
                 donate: bool = False) -> jnp.ndarray:
    """Run the planned forward as one jitted program.

    ``mesh`` must be the live mesh matching ``plan.mesh_axes`` (the Mesh
    object stays out of the cached IR).  ``activation`` is a STATIC jit
    argument hashed by identity — pass a stable callable
    (``jax.nn.relu``, a module-level function), never a fresh
    lambda/partial per call, or every call recompiles the whole fused
    program.  ``donate=True`` donates the input batch buffer to the
    program (streaming serving: the carry can reuse it, and the caller
    must hand a FRESH buffer to every call — `launch.batching.InputRing`);
    ignored when the platform the plan actually runs on — the mesh's
    devices when a mesh is bound, else the default backend
    (`donation_supported`) — does not implement donation (CPU).
    Fixed kernels (concrete ``jax.Array``s, no trace active, one device)
    feed the mapped layers the network's prepared shifted-weight blocks
    as program inputs, shared across every tier/ladder of the network
    (``exec.constants.fixed_constants``); traced or numpy kernels, and
    plans on a mesh of several devices, build the blocks inside the
    program.

    The call runs inside the host span ``repro.execute_plan`` (its
    ``batch`` is the tier, ``prepared`` whether the program reads
    prepared blocks): checks, the handle's lookup, hashing the static
    plan, and the jit dispatch.
    """
    with span("execute_plan", batch=x.shape[0]) as sp:
        _check_call(plan, kernels, x, mesh)
        constants = fixed_constants(plan, kernels, mesh)
        consts = None if constants is None else constants.weights
        note_call(plan, consts is not None)
        sp.set_metadata(prepared=consts is not None)
        if consts is not None:
            # a layer fed its blocks reads no kernel: keep it out of the
            # program's inputs and the lookahead fence
            kernels = [None if w is not None else k
                       for k, w in zip(kernels, consts)]
        fn = _execute_jit_donated if donate and donation_supported(mesh) \
            else _execute_jit
        return fn(plan, tuple(kernels), x, consts, mesh=mesh,
                  activation=activation)


def plan_compile_stats():
    """:func:`repro.launch.device.compile_stats` of the whole-forward
    programs and the program that prepares their blocks: what tracing,
    lowering and compiling (or loading) every plan `execute_plan` has
    run cost this process."""
    return compile_stats(_execute_jit.__name__,
                         _execute_jit_donated.__name__,
                         _prepare_blocks.__name__)


def execute_looped(plan: NetworkPlan, kernels: Sequence[jnp.ndarray],
                   x: jnp.ndarray, *, mesh=None,
                   activation=None) -> jnp.ndarray:
    """The pre-plan dispatch shape — one jit launch per layer, eager glue
    between — kept as the benchmark baseline `execute_plan` is measured
    against (same numerics, `len(plan.layers)` host dispatches per
    forward instead of one)."""
    _check_call(plan, kernels, x, mesh)
    return _forward(plan, tuple(kernels), x, mesh, activation, jitted=True)


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("mesh",))
def _execute_layerwise_jit(plan, kernels, xs, *, mesh=None):
    return tuple(_layer_conv(lp, x, k, mesh, jitted=False)
                 for lp, k, x in zip(plan.layers, kernels, xs))


def execute_layerwise(plan: NetworkPlan, kernels: Sequence[jnp.ndarray],
                      xs: Sequence[jnp.ndarray], *, mesh=None):
    """Every layer on its OWN input, fused into one jitted program — the
    plan counterpart of looping `apply_layer` over a stack that does not
    chain (several bench networks are representative layer *sets*, not
    chains).  One host dispatch instead of ``len(plan.layers)``."""
    if len(kernels) != len(plan.layers) or len(xs) != len(plan.layers):
        raise ValueError(f"{len(kernels)} kernels / {len(xs)} inputs for "
                         f"{len(plan.layers)} planned layers")
    return _execute_layerwise_jit(plan, tuple(kernels), tuple(xs),
                                  mesh=mesh)


#: The agreement two executions of the same sums in different orders
#: hold to (DESIGN.md §7): ``reassoc_error(a, b) <= REASSOC_RTOL``.
#: Sharded vs single-device programs and rematerialized vs stored
#: backward passes compute identical terms, but XLA fuses and orders
#: their f32 accumulations per program, so they agree to rounding, not
#: bit for bit.  A lost or doubled partial sum is O(1) off.
REASSOC_RTOL = 1e-5


def reassoc_error(a, b) -> float:
    """``max|a - b| / max|b|``: the error of ``a`` against ``b`` relative
    to ``b``'s largest magnitude (0 when both are all zero)."""
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    scale = float(np.max(np.abs(b))) if b.size else 0.0
    err = float(np.max(np.abs(a - b))) if b.size else 0.0
    return err / scale if scale else err


def execute_oracle(plan: NetworkPlan, kernels: Sequence[jnp.ndarray],
                   x: jnp.ndarray, *, activation=None) -> jnp.ndarray:
    """`lax.conv_general_dilated` composed over the SAME compiled chain
    — the DESIGN.md §5 oracle the plan executors are cross-checked
    against (pruned channels must be zeroed in ``kernels``)."""
    from repro.cnn.cim_conv import reference_conv2d
    if not plan.chained:
        raise ValueError("execute_oracle needs a chained plan")
    return _forward(
        plan, tuple(kernels), x, None, activation, jitted=True,
        conv=lambda lp, xp, k: reference_conv2d(
            lp.mapping.layer, xp, k, groups=lp.mapping.group))


def apply_layer(plan: NetworkPlan, i: int, x: jnp.ndarray,
                kernel: jnp.ndarray, *, mesh=None) -> jnp.ndarray:
    """Execute layer ``i`` of the plan stand-alone (jit entry per
    executor) — the `apply_cnn` path, where pooling / bias / activation
    plumbing between convs belongs to the model, not the plan."""
    return _layer_conv(plan.layers[i], x, kernel, mesh, jitted=True)
