"""`NetworkPlan` — one compiled execution-plan IR for all three executors.

The paper's speedups come from *scheduling*: window configs are searched
per layer, then executed across a fixed macro budget.  Before this module
every call site re-derived that schedule ad hoc — `mapped_net_apply`
walked a Python loop of per-layer super-steps, `serve_cnn` re-planned
mesh fitting per layer per request, and the reference / mapped / Pallas
executors each owned a private copy of the chaining + steps==cycles
logic.  `compile_plan` lowers a `NetworkMapping` **once** into a static
per-layer plan; `execute_plan` (exec/run.py) then runs the whole forward
as ONE jitted program.

Compilation is a staged **pass pipeline** over a `PlanDraft` — each
pass takes the draft and returns an updated one, so new analyses slot
in without touching dispatch::

    validate ─ resolve_executors ─ check_glue ─ estimate_memory
             ─ segment ─ schedule ─ (freeze → NetworkPlan)

* **validate** — batch/mesh divisibility (a ragged batch is refused
  here: pad it first);
* **resolve_executors** — per-layer executor legality (sdk
  realizability, matmul op match) and the sharding decision
  (`macro_mesh_fits`), so dispatch never re-fits;
* **check_glue** — inter-layer glue: plain chain / DenseNet concat
  classified from channel arithmetic (exec/glue.py) for CNNs, or the
  mapping's explicit `GlueSpec` tuple validated by carry simulation
  (residual saves, Inception branches and joins) — a mis-chained
  network fails at compile, not mid-forward;
* **estimate_memory** — per-layer live-activation + shifted-weight
  byte estimates from the LayerMapping itself (exec/memory.py);
* **segment** — rematerialization boundaries under the requested
  peak-memory budget (exec/remat.py; concat groups never split);
* **schedule** — the super-step schedule (`LayerSchedule`) with the
  steps==cycles assertion evaluated here, at compile time, instead of
  on every dispatch.

Plans are frozen, hashable (static jit arguments) and picklable; they
join the memo result/disk cache keyed on mapping + resolved policy +
mesh shape + batch + remat spec (`core/memo.cached_plan`), so a serving
replica compiles each distinct (network, mesh, batch) once per process
— or never, with a warm disk cache.  See DESIGN.md §8 and §13.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Callable, Optional, Sequence, Tuple, Union

import jax

from repro.core import memo
from repro.core.types import GlueSpec, NetworkMapping
from repro.cnn.mapped_net import LayerSchedule, check_steps, layer_schedule
from repro.kernels.im2win_conv import (default_vmem_budget, sdk_conv_cycles,
                                       sdk_refusal)
from repro.launch.sharding import macro_mesh_fits
from . import memory as memlib
from . import remat as rematlib
from .glue import glue_floats, resolve_chain

#: Executors a plan can dispatch a layer to.  "matmul" is the MXU path
#: for ``op="matmul"`` layers (kernels/matmul_exec.py:
#: tetris_matmul / grouped_matmul); the conv executors also accept
#: matmul layers as the degenerate 1x1 conv they are.
EXECUTORS = ("reference", "mapped", "sdk", "matmul")

#: Anything compile_plan accepts as a policy: one name (or "auto") for
#: every layer, a per-layer sequence of names, or a callable
#: ``LayerMapping -> name``.
PolicyLike = Union[str, Sequence[str], Callable]


@dataclass(frozen=True)
class LayerPlan:
    """Compiled execution of ONE layer — everything dispatch used to
    re-derive, fixed at compile time."""

    mapping: object             # LayerMapping (frozen, hashable)
    executor: str               # "reference" | "mapped" | "sdk" | "matmul"
    schedule: LayerSchedule     # steps==cycles evidence (compile-time)
    glue: GlueSpec              # structured inter-layer glue (core.types)
    carry_c: int                # channels entering this layer
    use_mesh: bool              # shard_map vs vmap, decided at compile
    interpret: bool = False     # sdk: pallas interpret mode (off-TPU)
    block: str = "auto"         # sdk: tiling mode
    vmem_budget: int = 8 * 1024 * 1024  # sdk: resolved byte budget
    act_bytes: int = 0          # memory pass: saved input activation
    weight_bytes: int = 0       # memory pass: shifted-weight prep
    note: str = ""              # why the compiled sdk kernel was refused

    @property
    def mem_bytes(self) -> int:
        """Live bytes this layer pins during an unremat'd backward."""
        return self.act_bytes + self.weight_bytes


@dataclass(frozen=True)
class NetworkPlan:
    """Static whole-network execution plan (a hashable jit argument).

    ``mesh_axes`` records the compile mesh's (name, size) shape — the
    Mesh object itself stays out of the IR so plans hash, pickle, and
    disk-cache; `execute_plan` re-binds the live mesh and validates it
    against these axes.  ``batch`` is the batch the sharding decisions
    were made for (None: no data-axis sharding was requested).
    """

    net: NetworkMapping
    layers: Tuple[LayerPlan, ...]
    mesh_axes: Optional[Tuple[Tuple[str, int], ...]]
    batch: Optional[int]
    chained: bool = True
    #: cross-layer pipeline depth of the fused program (exec/run.py):
    #: kernels of layers beyond ``i + 1 + lookahead`` are fenced behind
    #: layer i's carry.  A compile-time field (formerly the module
    #: constant ``_LOOKAHEAD``) so the autotuner — and users — can sweep
    #: it without monkeypatching; each value is its own plan, so
    #: changing it recompiles the fused program exactly once per value.
    lookahead: int = 1
    #: rematerialization segments — half-open (start, end) layer ranges
    #: chosen by the segment pass; None when remat was off (the PR-4-era
    #: single-program shape).  `execute_plan` wraps each segment in
    #: `jax.checkpoint` when there is more than one.
    segments: Optional[Tuple[Tuple[int, int], ...]] = None

    @property
    def executors(self) -> Tuple[str, ...]:
        return tuple(lp.executor for lp in self.layers)

    @property
    def total_steps(self) -> int:
        """Compile-time super-step total == NetworkMapping.total_cycles."""
        return sum(lp.schedule.steps for lp in self.layers)

    @property
    def spans(self) -> Tuple[Tuple[int, int], ...]:
        """The segment ranges dispatch iterates — one whole-net span
        when the segment pass did not run / remat is off."""
        if self.segments is not None:
            return self.segments
        return ((0, len(self.layers)),)

    @property
    def layer_memory(self) -> Tuple[memlib.LayerMemory, ...]:
        return tuple(memlib.LayerMemory(lp.mapping.layer.name,
                                        lp.act_bytes, lp.weight_bytes)
                     for lp in self.layers)

    @property
    def peak_bytes(self) -> int:
        """Peak live-byte estimate of training through this plan *as
        segmented* (exec/memory.py peak model)."""
        return memlib.peak_bytes(self.layer_memory, self.spans)

    @property
    def unremat_peak_bytes(self) -> int:
        """What the peak would be with every layer's residuals live at
        once — the remat-off baseline the frontier is measured against."""
        return memlib.total_bytes(self.layer_memory)

    @property
    def placements(self) -> int:
        """Placement ops a forward runs in its mapped layers (one per
        tile, `LayerSchedule.placements`)."""
        return sum(lp.schedule.placements for lp in self.layers
                   if lp.executor == "mapped")

    @property
    def glue_bytes_per_image(self) -> int:
        """Float32 bytes per image the glue writes between the mapped
        layers of a chained plan — pads and crops, pools, concats —
        counted from the static shapes (`glue.glue_floats`)."""
        if not self.chained:
            return 0
        return memlib.ITEMSIZE * glue_floats(
            [lp.mapping.layer for lp in self.layers],
            [lp.glue for lp in self.layers])

    def describe(self) -> str:
        execs = ",".join(
            f"{lp.mapping.layer.name}:{lp.executor}"
            + (f"[{lp.note}]" if lp.note else "")
            + (f"(p={lp.schedule.placements})" if lp.executor == "mapped"
               else "")
            for lp in self.layers)
        tag = ("x".join(f"{n}={s}" for n, s in self.mesh_axes)
               if self.mesh_axes else "vmap")
        seg = f" segments={len(self.segments)}" if self.segments else ""
        return (f"plan[{self.net.name}] layers={len(self.layers)} "
                f"steps={self.total_steps} placements={self.placements} "
                f"glue_bytes={self.glue_bytes_per_image} "
                f"mesh={tag} "
                f"lookahead={self.lookahead} "
                f"peak_mem={self.peak_bytes / 1e6:.1f}MB{seg} ({execs})")

    def describe_memory(self) -> str:
        """Per-layer memory-pass estimates, one line per layer, with
        segment boundaries marked — the frontier, inspectable without
        running the trainer."""
        starts = {s for s, _ in self.spans[1:]}
        lines = [f"plan[{self.net.name}] "
                 f"peak={self.peak_bytes / 1e6:.1f}MB "
                 f"unremat={self.unremat_peak_bytes / 1e6:.1f}MB "
                 f"segments={len(self.spans)}"]
        for i, lp in enumerate(self.layers):
            cut = " <- segment" if i in starts else ""
            lines.append(
                f"  {lp.mapping.layer.name}: act="
                f"{lp.act_bytes / 1e6:.2f}MB weights="
                f"{lp.weight_bytes / 1e6:.2f}MB{cut}")
        return "\n".join(lines)


def mesh_axes(mesh) -> Optional[Tuple[Tuple[str, int], ...]]:
    """Canonical (name, size) shape of a mesh — the form stored in the
    IR, used in the plan cache key, and validated at execute time (one
    definition so the three cannot drift)."""
    if mesh is None:
        return None
    return tuple((str(n), int(s)) for n, s in mesh.shape.items())


def _why_not_sdk(mapping, batch: Optional[int] = None, *,
                 block: str = "auto", vmem_budget: Optional[int] = None,
                 compiled: bool = True) -> Optional[str]:
    """Why the sdk executor cannot run ``mapping`` — None when it can.
    The one home of that decision (auto heuristic, legality pass,
    tuner seeds): sdk runs every pass and every group sequentially, so
    it can only stand in for a mapping that owes no macro/group
    parallelism; compiled (Mosaic, not interpret) it must also fit the
    static TPU tiling rule at this batch (`sdk_refusal`)."""
    if sdk_conv_cycles(mapping) != mapping.cycles:
        return (f"sdk runs passes/groups sequentially in "
                f"{sdk_conv_cycles(mapping)} grid steps, not the mapping's "
                f"{mapping.cycles} cycles (sub-grid "
                f"{mapping.sub_grid.r}x{mapping.sub_grid.c}, "
                f"{mapping.group_rounds} group rounds)")
    if compiled:
        why = sdk_refusal(mapping, batch, block=block,
                          vmem_budget=vmem_budget)
        if why is not None:
            return f"TPU tiling rule: {why}"
    return None


def _auto_executor(mapping, *, backend: str, batch: Optional[int] = None,
                   block: str = "auto",
                   vmem_budget: Optional[int] = None) -> str:
    """Per-layer heuristic: the Pallas MXU paths on TPU — ``"matmul"``
    for op="matmul" layers (tetris_matmul / grouped_matmul own the
    tiling), ``"sdk"`` for conv layers it can run at this batch
    (`_why_not_sdk`; its ``block="auto"`` tiling handles the VMEM
    budget per layer size); the macro-parallel executor whenever a
    non-degenerate sub-grid must be realized; otherwise the
    placement-batched reference path (fewest ops — fastest
    off-accelerator)."""
    if backend == "tpu":
        if getattr(mapping.layer, "op", "conv") == "matmul":
            return "matmul"
        if _why_not_sdk(mapping, batch, block=block,
                        vmem_budget=vmem_budget) is None:
            return "sdk"
    if mapping.sub_grid.p > 1 or mapping.group_rounds < mapping.group:
        return "mapped"
    return "reference"


def _resolve_policy(policy: PolicyLike, net: NetworkMapping, *,
                    backend: str, batch: Optional[int] = None,
                    block: str = "auto",
                    vmem_budget: Optional[int] = None) -> Tuple[str, ...]:
    if callable(policy):
        per_layer = [policy(m) for m in net.layers]
    elif isinstance(policy, str):
        per_layer = [policy] * len(net.layers)
    else:
        per_layer = list(policy)
        if len(per_layer) != len(net.layers):
            raise ValueError(
                f"policy lists {len(per_layer)} executors for "
                f"{len(net.layers)} layers")
    out = []
    for name, m in zip(per_layer, net.layers):
        if name == "auto":
            name = _auto_executor(m, backend=backend, batch=batch,
                                  block=block, vmem_budget=vmem_budget)
        if name not in EXECUTORS:
            raise ValueError(f"unknown executor {name!r} "
                             f"(expected one of {EXECUTORS} or 'auto')")
        out.append(name)
    return tuple(out)


# ---------------------------------------------------------------------------
# the pass pipeline


@dataclass(frozen=True)
class PlanDraft:
    """The intermediate the compile passes thread — compile_plan's
    resolved inputs plus one field per analysis, each filled by its
    pass and read by later ones.  Frozen: passes return an updated copy
    (`dataclasses.replace`), never mutate."""

    net: NetworkMapping
    execs: Tuple[str, ...]
    mesh: object                    # the LIVE mesh (not in the final IR)
    batch: Optional[int]
    chained: bool
    interpret: bool
    block: str
    vmem_budget: int
    lookahead: int
    remat: object                   # canonical spec (exec.remat)
    # pass products
    use_mesh: Optional[Tuple[bool, ...]] = None        # resolve_executors
    notes: Optional[Tuple[str, ...]] = None            # resolve_executors
    glue: Optional[Tuple[GlueSpec, ...]] = None        # check_glue
    carries: Optional[Tuple[int, ...]] = None          # check_glue
    mem: Optional[Tuple[memlib.LayerMemory, ...]] = None  # estimate_memory
    segments: Optional[Tuple[Tuple[int, int], ...]] = None  # segment
    schedules: Optional[Tuple[LayerSchedule, ...]] = None   # schedule


def pass_validate(d: PlanDraft) -> PlanDraft:
    """Whole-plan input legality (per-layer legality lives with the
    passes that own the facts)."""
    if (d.mesh is not None and "data" in d.mesh.axis_names
            and d.batch is not None and d.batch % d.mesh.shape["data"]):
        # refuse rather than silently vmap the whole net: ragged batches
        # must pad to the data axis (launch.mesh.pad_to_data_axis /
        # serve_cnn pad-and-mask)
        raise ValueError(
            f"batch {d.batch} does not divide the mesh data axis "
            f"{d.mesh.shape['data']} — pad the batch to "
            f"pad_to_data_axis(batch, mesh) or drop the data axis")
    return d


def pass_resolve_executors(d: PlanDraft) -> PlanDraft:
    """Executor legality per layer + the sharding decision.  An explicit
    ``"sdk"`` that `_why_not_sdk` refuses raises here; compiled
    (non-interpret) plans note the refusal on every conv layer either
    way, so describe() says why a layer is not on sdk."""
    use, notes = [], []
    for m, ex in zip(d.net.layers, d.execs):
        lay = m.layer
        why = _why_not_sdk(m, d.batch, block=d.block,
                           vmem_budget=d.vmem_budget,
                           compiled=not d.interpret)
        if ex == "sdk" and why is not None:
            raise ValueError(f"{lay.name}: executor 'sdk' refused: {why}"
                             f" — use 'mapped'")
        noted = (why is not None and not d.interpret
                 and getattr(lay, "op", "conv") == "conv")
        notes.append(f"sdk refused: {why}" if noted else "")
        if ex == "matmul" and getattr(lay, "op", "conv") != "matmul":
            raise ValueError(
                f"{lay.name}: executor 'matmul' requires op='matmul' "
                f"(this layer is op={getattr(lay, 'op', 'conv')!r})")
        use.append(ex == "mapped"
                   and macro_mesh_fits(d.mesh, m.sub_grid.r, m.sub_grid.c,
                                       batch=d.batch))
    return replace(d, use_mesh=tuple(use), notes=tuple(notes))


def pass_check_glue(d: PlanDraft) -> PlanDraft:
    """Classify / validate inter-layer glue and the carry channel count
    entering each layer."""
    net = d.net
    n = len(net.layers)
    if not d.chained:
        return replace(
            d, glue=tuple(GlueSpec(kind="layerwise") for _ in range(n)),
            carries=tuple(m.layer.ic for m in net.layers))
    glue, carries = [], []
    carry_c = net.layers[0].layer.ic
    saved: list = []                # channel widths of GlueSpec.save stack
    branches: list = []             # (c, h, w) of the open module branches
    for i, m in enumerate(net.layers):
        lay = m.layer
        carries.append(carry_c)
        if net.glue is not None:
            spec = net.glue[i]
            carry_c, saved, branches = _check_explicit_glue(
                net, i, spec, carry_c, saved, branches)
        else:
            if i + 1 < n:
                nxt = net.layers[i + 1].layer
                spec = GlueSpec(kind=resolve_chain(
                    lay.name, lay.oc, carry_c, nxt.name, nxt.ic))
            else:
                spec = GlueSpec(kind="last")
            carry_c = net.layers[i + 1].layer.ic if i + 1 < n else lay.oc
        glue.append(spec)
    if branches:
        raise ValueError(
            f"{net.name}: a module is left open at the end: "
            f"{len(branches)} branch output(s) never joined")
    if net.glue is not None and saved:
        raise ValueError(
            f"{net.name}: {len(saved)} saved residual input(s) never "
            f"consumed by a kind='residual' glue")
    # carries[i] == layers[i].ic in every valid plan (the simulation
    # above raises otherwise) — recorded explicitly so later passes
    # read the glue pass's product, not channel arithmetic of their own
    return replace(d, glue=tuple(glue), carries=tuple(carries))


def pass_estimate_memory(d: PlanDraft) -> PlanDraft:
    """Per-layer live-byte estimates (exec/memory.py).  ``batch=None``
    plans price a single example — the estimate scales linearly, and
    the segment boundaries it drives depend only on the ratios."""
    mem = memlib.network_memory(d.net, d.carries,
                                d.batch if d.batch else 1)
    return replace(d, mem=mem)


def pass_segment(d: PlanDraft) -> PlanDraft:
    """Choose rematerialization boundaries (exec/remat.py).  Chained
    plans cut only at the glue pass's legal boundaries; layerwise plans
    (`apply_cnn`, which owns its own glue) may cut anywhere."""
    if d.remat is None:
        return d                    # remat off: segments stays None
    if d.chained:
        allowed = rematlib.allowed_cuts(d.glue)
    else:
        allowed = tuple(range(len(d.net.layers) - 1))
    return replace(d, segments=rematlib.plan_segments(d.mem, allowed,
                                                      d.remat))


def pass_schedule(d: PlanDraft) -> PlanDraft:
    """Super-step schedules, with steps==cycles asserted per layer —
    at compile time, never at dispatch."""
    scheds = []
    for m in d.net.layers:
        check_steps(m)
        scheds.append(layer_schedule(m))
    return replace(d, schedules=tuple(scheds))


#: The pipeline, in order.  Each pass is PlanDraft -> PlanDraft; new
#: analyses insert here without touching dispatch or the freeze step.
PASSES: Tuple[Callable[[PlanDraft], PlanDraft], ...] = (
    pass_validate, pass_resolve_executors, pass_check_glue,
    pass_estimate_memory, pass_segment, pass_schedule)


def _freeze(d: PlanDraft) -> NetworkPlan:
    """Assemble the frozen IR from a fully-analyzed draft."""
    layers = tuple(
        LayerPlan(mapping=m, executor=ex, schedule=sch, glue=g,
                  carry_c=c, use_mesh=um, interpret=d.interpret,
                  block=d.block, vmem_budget=d.vmem_budget,
                  act_bytes=mm.act_bytes, weight_bytes=mm.weight_bytes,
                  note=note)
        for m, ex, sch, g, c, um, mm, note in zip(
            d.net.layers, d.execs, d.schedules, d.glue, d.carries,
            d.use_mesh, d.mem, d.notes))
    return NetworkPlan(net=d.net, layers=layers,
                       mesh_axes=mesh_axes(d.mesh), batch=d.batch,
                       chained=d.chained, lookahead=d.lookahead,
                       segments=d.segments)


def _compile(net: NetworkMapping, execs: Tuple[str, ...], mesh,
             batch: Optional[int], chained: bool, interpret: bool,
             block: str, vmem_budget: int, lookahead: int,
             remat_spec=None) -> NetworkPlan:
    draft = PlanDraft(net=net, execs=execs, mesh=mesh, batch=batch,
                      chained=chained, interpret=interpret, block=block,
                      vmem_budget=vmem_budget, lookahead=lookahead,
                      remat=remat_spec)
    for p in PASSES:
        draft = p(draft)
    return _freeze(draft)


def _check_explicit_glue(net: NetworkMapping, i: int, spec: GlueSpec,
                         carry_c: int, saved: list, branches: list):
    """Compile-time channel simulation of one explicit-glue step: what
    `resolve_chain` does for inferred CNN glue, generalized to the
    save/residual stack, the attention stage and Inception modules (a
    branch hands the next layer the saved module input; a join
    concatenates the branches).  Returns the carry channel count
    entering layer i+1 and the updated saved stack and open branches —
    raising the mis-chaining error here, never mid-forward."""
    lay = net.layers[i].layer
    last = i + 1 == len(net.layers)
    if lay.ic != carry_c:
        raise ValueError(
            f"{lay.name}: glue carries {carry_c} channels into a layer "
            f"with ic={lay.ic}")
    if spec.kind == "layerwise" or (spec.kind == "last" and not last):
        raise ValueError(
            f"{lay.name}: glue kind {spec.kind!r} is invalid for chained "
            f"layer {i} of {len(net.layers)}")
    out_c = lay.oc
    if spec.post == "attention":
        hq, hkv, hd = spec.heads
        if getattr(lay, "op", "conv") != "matmul" \
                or lay.oc != (hq + 2 * hkv) * hd:
            raise ValueError(
                f"{lay.name}: post='attention' with heads "
                f"({hq}q, {hkv}kv, {hd}d) needs an op='matmul' layer "
                f"with oc={(hq + 2 * hkv) * hd}, got op="
                f"{getattr(lay, 'op', 'conv')!r} oc={lay.oc}")
        out_c = hq * hd
    out = (out_c, lay.o_h, lay.o_w)
    saved = list(saved)
    branches = list(branches)
    if spec.save:
        saved.append(carry_c)
    if spec.kind == "residual":
        if not saved:
            raise ValueError(f"{lay.name}: kind='residual' with no saved "
                             f"input (no earlier glue set save=True)")
        res_c = saved.pop()
        if res_c != out_c:
            raise ValueError(
                f"{lay.name}: residual add of {res_c} saved channels "
                f"onto {out_c} output channels")
        nxt_c = out_c
    elif spec.kind == "concat":
        nxt_c = carry_c + out_c
    elif spec.kind == "branch":
        if not saved:
            raise ValueError(f"{lay.name}: kind='branch' with no saved "
                             f"module input (no earlier glue set "
                             f"save=True)")
        branches.append(out)
        nxt_c = saved[-1]
    elif spec.kind == "join":
        if not branches:
            raise ValueError(f"{lay.name}: kind='join' with no open "
                             f"branches (no earlier glue set "
                             f"kind='branch')")
        if not saved:
            raise ValueError(f"{lay.name}: kind='join' with no saved "
                             f"module input to pop")
        branches.append(out)
        if len({b[1:] for b in branches}) > 1:
            raise ValueError(
                f"{lay.name}: join concatenates branch outputs of "
                f"different sizes {[b[1:] for b in branches]}")
        nxt_c = sum(b[0] for b in branches)
        branches = []
        saved.pop()
    else:                               # "chain" or final "last"
        nxt_c = out_c
    if not last and net.layers[i + 1].layer.ic != nxt_c:
        nxt = net.layers[i + 1].layer
        raise ValueError(
            f"cannot chain {lay.name} ({spec.kind}, {nxt_c} carry "
            f"channels) into {nxt.name} (ic={nxt.ic})")
    return nxt_c, saved, branches


def compile_plan(net: NetworkMapping, *,
                 executor_policy: PolicyLike = "auto",
                 mesh=None, batch: Optional[int] = None,
                 chained: bool = True,
                 interpret: Optional[bool] = None,
                 block: Optional[str] = None,
                 vmem_budget: Optional[int] = None,
                 lookahead: Optional[int] = None,
                 remat: rematlib.RematSpec = None) -> NetworkPlan:
    """Lower ``net`` once into a :class:`NetworkPlan`.

    ``executor_policy`` — ``"auto"`` (per-layer heuristic, see
    `_auto_executor`), ``"tuned"`` (the measured-feedback autotuner's
    persisted winner for this net / device fleet / batch — see
    `repro.tune`; falls back to ``"auto"`` when nothing has been tuned),
    one executor name for every layer, a per-layer sequence, or a
    callable ``LayerMapping -> name``.  ``mesh``/``batch``
    fix the sharding decisions (`macro_mesh_fits` per layer, evaluated
    here, never at dispatch); a batch that does not divide the mesh's
    data axis is refused here — pad it first (`mesh.pad_to_data_axis`).
    ``chained=False`` compiles a *layerwise* plan — per-layer executor
    dispatch without inter-layer glue (the `apply_cnn` path, which owns
    its own pooling/bias plumbing); such plans cannot be passed to
    `execute_plan`.

    ``lookahead`` (default 1) is the fused program's cross-layer
    pipeline depth; ``vmem_budget`` (default: the
    ``REPRO_SDK_VMEM_BUDGET`` environment variable, else 8 MiB) bounds
    the sdk executor's ``block="auto"`` whole-array working set.

    ``remat`` asks the segment pass for rematerialization boundaries:
    ``None``/``"off"`` (no segmentation — the default), ``"auto"``
    (budget from ``REPRO_TRAIN_MEM_BUDGET`` bytes if set, else the
    sqrt-segments heuristic), an ``int`` peak-byte budget, or an
    explicit sequence of boundary layer indices (cut *after* each;
    illegal cuts — mid concat group, over an outstanding residual —
    raise).  `execute_plan` then wraps each segment in `jax.checkpoint`
    (exec/remat.py).

    With ``executor_policy="tuned"`` any of ``lookahead`` / ``block`` /
    ``vmem_budget`` / ``remat`` left unset take the tuned values (pass
    ``remat="off"`` to force remat off under a tuned policy).

    Every layer's executed schedule is asserted equal to its
    ``LayerMapping.cycles`` here (compile time), and a mis-chained
    network raises the chaining error here too.  Results are memoized —
    in memory and, when a disk cache is configured, across processes —
    keyed on (net, resolved policy, mesh shape, batch, flags, remat).
    """
    if not net.layers:
        raise ValueError(f"{net.name}: cannot plan an empty network")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if executor_policy == "tuned":
        # lazy import: repro.tune compiles plans, so the dependency
        # must point tune -> exec at module scope, not both ways
        from repro.tune import tuned_config
        cfg = tuned_config(net, batch=batch)
        if cfg is None:
            executor_policy = "auto"
        else:
            executor_policy = cfg.candidate.policy
            if lookahead is None:
                lookahead = cfg.candidate.lookahead
            if block is None:
                block = cfg.candidate.block
            if vmem_budget is None:
                vmem_budget = cfg.candidate.vmem_budget
            if remat is None:
                remat = getattr(cfg.candidate, "remat", None)
    if lookahead is None:
        lookahead = 1
    if lookahead < 0:
        raise ValueError(f"lookahead must be >= 0, got {lookahead}")
    if block is None:
        block = "auto"
    if vmem_budget is None:
        vmem_budget = default_vmem_budget()
    remat_spec = rematlib.canonical_remat(remat)
    execs = _resolve_policy(executor_policy, net,
                            backend=jax.default_backend(), batch=batch,
                            block=block, vmem_budget=vmem_budget)
    key = (net, execs, mesh_axes(mesh), batch, chained, interpret, block,
           vmem_budget, lookahead, remat_spec)

    def _compile_counted():
        _note_compile(key)
        return _compile(net, execs, mesh, batch, chained, interpret,
                        block, vmem_budget, lookahead, remat_spec)

    return memo.cached_plan(key, _compile_counted)


#: Actual `_compile` lowerings per cache key — cache hits (in-memory or
#: disk) do NOT count.  The serving acceptance tests assert every tier
#: of a plan ladder compiles exactly once per process
#: (tests/test_serve_cnn.py); bounded like im2win_conv._trace_counts so
#: a long-lived process cannot grow it without limit.
_compile_counts: dict = {}
_COMPILE_COUNT_LIMIT = 512


def _note_compile(key) -> None:
    if key not in _compile_counts:
        while len(_compile_counts) >= _COMPILE_COUNT_LIMIT:
            del _compile_counts[next(iter(_compile_counts))]
        _compile_counts[key] = 0
    _compile_counts[key] += 1


def compile_counts(*, net: Optional[NetworkMapping] = None,
                   batch: Optional[int] = None) -> dict:
    """Copy of the per-key compile counters, optionally filtered to one
    network mapping and/or plan batch — ``compile_counts(net=nm)``
    values of all 1 prove each (policy, mesh, batch) lowered once."""
    out = {}
    for key, n in _compile_counts.items():
        if net is not None and key[0] != net:
            continue
        if batch is not None and key[3] != batch:
            continue
        out[key] = n
    return out


# a cleared memo cache recompiles, so the counters reset with it —
# "each tier compiled once" stays meaningful per cache generation
memo.register_cache_clear(_compile_counts.clear)
