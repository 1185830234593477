"""Prepared plan constants: a network's shifted-weight blocks, built once
per kernel set and fed to every tier of every plan of that network.

The mapped executor multiplies each window load by a blocked
shifted-and-duplicated weight matrix (`cnn/mapped_net._tile_weights`,
the Fig 5 blocks).  The blocks depend only on the mapping and the
kernels, never on the input or the batch, so building them inside every
forward rewrites the same (mostly zero) matrices on every call — and a
ladder pays for it in every tier's program.  Real CIM hardware programs
its weights into the arrays once; so does this module.

One rule, observed per call by ``execute_plan`` (:func:`fixed_constants`):

* **prepared whenever the kernels are fixed** — every kernel a concrete
  ``jax.Array`` (immutable) and no trace active: the call looks up or
  materializes the network's :class:`PlanConstants` and the program
  reads the blocks as inputs;
* **in-trace when traced** — kernels that are tracers (``jax.grad`` of a
  training loss, a call inside a user's ``jit``, lowering from
  ``ShapeDtypeStruct``s) or mutable numpy arrays keep the in-program
  build behind the cross-layer lookahead fence (exec/run.py), so
  gradients and HLO are those of the in-trace path;
* **in-trace on a mesh of several devices** — the blocks would sit on
  one device and be resharded onto the mesh on every call; the
  in-program build makes them where the sharding puts them;
* **in-trace when too large** — a handle keeps every layer's blocks live,
  where the in-trace build keeps at most the lookahead window's; when
  the blocks (their exact bytes from ``jax.eval_shape``, no device work)
  together with the handles already held exceed half the device's
  ``memory_stats()["bytes_limit"]`` (where the backend reports one) the
  call keeps the in-trace build.

Handles live in ``memo.cached_constants`` keyed on ``(net mapping,
resolved executors, kernel ids)``, so every tier of every ladder of a
network shares one.  Each entry holds weak references to its kernels
whose callbacks drop it when any of them dies: an id is never reused
while its entry lives, and new kernels (``Server.reseed``, a training
step) materialize anew.  The blocks come out of ONE jitted program per
network (:func:`_prepare_blocks`, the mapping static), so set-up pays
one compile, which JAX's persistent cache serves like the tier
programs'.

The blocks arrive as program *inputs*, so the lookahead fence does not
thread them: it bounds in-program prep, and there is none left.

Counters: ``constant_counts`` gives materializations per cache key (hits
do not count); :func:`prepared_stats` gives, per process, prepared calls,
in-trace calls, materializations and memory fallbacks.
"""
from __future__ import annotations

import functools
import weakref
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import jax

from repro.core import memo
from repro.core.types import NetworkMapping

from .plan import NetworkPlan


@dataclass(frozen=True)
class PlanConstants:
    """Prepared constants for every plan compiled from one network
    mapping: ``weights[i]`` is layer i's per-tile/per-shape blocked
    shifted-weight matrices (`cnn/mapped_net.prepared_layer_weights`)
    when the plan runs that layer on the ``"mapped"`` executor, else
    ``None`` (the reference/sdk/matmul executors consume raw kernels —
    an op="matmul" layer's weight matrix needs no shifted duplication).
    Valid for ANY batch/tier of the network — the blocks are input- and
    batch-independent.  ``nbytes`` is their size on the device."""

    weights: Tuple[Optional[Tuple], ...]
    nbytes: int


@functools.partial(jax.jit, static_argnums=0)
def _prepare_blocks(mappings, kernels):
    """Every mapped layer's blocks in one program: ``mappings[i]`` is
    layer i's LayerMapping where it runs ``"mapped"``, else None (and
    so is ``kernels[i]``)."""
    from repro.cnn.mapped_net import prepared_layer_weights
    return tuple(None if m is None else prepared_layer_weights(m, k)
                 for m, k in zip(mappings, kernels))


_stats = dict.fromkeys(("prepared_calls", "in_trace_calls",
                        "materializations", "memory_fallbacks"), 0)


def prepared_stats() -> Dict[str, int]:
    """Copy of this process's counters: ``execute_plan`` calls fed
    prepared blocks (``prepared_calls``) and calls of plans with a mapped
    layer that built them in the program (``in_trace_calls``); handles
    materialized and handles refused for memory."""
    return dict(_stats)


def note_call(plan: NetworkPlan, prepared: bool) -> None:
    """Count one ``execute_plan`` call (a plan with no mapped layer has
    no blocks to build either way)."""
    if prepared:
        _stats["prepared_calls"] += 1
    elif "mapped" in plan.executors:
        _stats["in_trace_calls"] += 1


def _bytes_limit(kernels: Sequence) -> Optional[int]:
    """``bytes_limit`` of the device the kernels live on, where the
    backend reports one (the CPU backend does not)."""
    stats = next(iter(kernels[0].devices())).memory_stats()
    return stats.get("bytes_limit") if stats else None


def held_bytes() -> int:
    """Device bytes of the handles the cache holds."""
    return sum(h.nbytes for h in memo.held_constants()
               if isinstance(h, PlanConstants))


def _materialize(plan: NetworkPlan, kernels: Sequence,
                 key) -> Optional[PlanConstants]:
    """The handle, or None when its blocks and the handles already held
    would take more than half the device's memory (the call then keeps
    the in-trace build)."""
    if len(kernels) != len(plan.layers):
        raise ValueError(f"{len(kernels)} kernels for "
                         f"{len(plan.layers)} planned layers")
    mappings = tuple(lp.mapping if lp.executor == "mapped" else None
                     for lp in plan.layers)
    ks = tuple(None if m is None else k for m, k in zip(mappings, kernels))
    nbytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(
        _prepare_blocks.eval_shape(mappings, ks)))
    limit = _bytes_limit(kernels)
    if limit is not None and nbytes + held_bytes() > limit // 2:
        _stats["memory_fallbacks"] += 1
        return None
    _note_materialize(key)
    _stats["materializations"] += 1
    return PlanConstants(weights=_prepare_blocks(mappings, ks),
                         nbytes=nbytes)


def fixed_constants(plan: NetworkPlan, kernels: Sequence,
                    mesh=None) -> Optional[PlanConstants]:
    """The prepared handle ``execute_plan`` feeds the plan: None unless
    the plan has a mapped layer, runs on one device, no trace is active,
    every kernel is a ``jax.Array`` and the memo layer is on; then the
    cached handle for these kernel objects, materialized on first use
    (None if it would not fit)."""
    if "mapped" not in plan.executors or not memo.enabled() \
            or (mesh is not None and mesh.size > 1) \
            or not jax.core.trace_ctx.is_top_level() \
            or not all(isinstance(k, jax.Array) for k in kernels):
        return None
    key = (plan.net, plan.executors, tuple(map(id, kernels)))

    def build():
        _watch(key, kernels)
        return _materialize(plan, kernels, key)
    return memo.cached_constants(("consts",) + key, build)


def _watch(key, kernels: Sequence) -> None:
    """Drop the entry under ``key`` when any of its kernels dies."""
    for k in kernels:
        weakref.finalize(k, _forget, key)


def _forget(key) -> None:
    _constant_counts.pop(key, None)
    memo.drop_constants(("consts",) + key)


#: Actual materializations per (net, executors, kernel ids) — cache hits
#: do NOT count.  The tests assert one materialization per network
#: however many tiers consume the handle; bounded like
#: exec/plan._compile_counts so a long-lived process cannot grow it.
_constant_counts: dict = {}
_CONSTANT_COUNT_LIMIT = 256


def _note_materialize(key) -> None:
    if key not in _constant_counts:
        while len(_constant_counts) >= _CONSTANT_COUNT_LIMIT:
            del _constant_counts[next(iter(_constant_counts))]
        _constant_counts[key] = 0
    _constant_counts[key] += 1


def constant_counts(*, net: Optional[NetworkMapping] = None) -> dict:
    """Copy of the per-key materialization counters, optionally filtered
    to one network mapping — ``constant_counts(net=nm)`` of length 1
    with value 1 proves the network's constants were prepared once and
    shared across every tier that used them."""
    out = {}
    for key, n in _constant_counts.items():
        if net is not None and key[0] != net:
            continue
        out[key] = n
    return out


def _reset() -> None:
    _constant_counts.clear()
    for k in _stats:
        _stats[k] = 0


# a cleared memo cache re-materializes, so the counters reset with it
memo.register_cache_clear(_reset)
