"""The names the program gives its work in a profiler trace.

On the device, every op of a planned forward carries its layer and its
phase in the JAX op path (``jit(_execute_jit)/L03.CNN8-5/macro/...``):
``exec/run._forward`` opens the scope ``L{i:02d}.{name}`` around planned
layer ``i``, and each executor opens one of :data:`PHASES` around each
part of its work.  Named scopes change only HLO metadata, never the
program.

On the host, :func:`span` marks the program's own calls (``repro.pop``,
``repro.plan_for``, ``repro.execute_plan``) on the profiler's clock.
With no profiler session running, a span only checks for one.
"""
from __future__ import annotations

import jax

#: The phases of an executor's work inside a layer's scope, each a
#: named scope of its own (never nested in another):
#:
#: * ``weights`` — the shifted-and-duplicated weight matrix built inside
#:   the forward (absent when the plan feeds prepared weights);
#: * ``patches`` — gathering the input windows each array pass reads;
#: * ``macro`` — the array passes themselves: the matmul or the Pallas
#:   kernel (and the cross-device ``psum`` under a mesh);
#: * ``place`` — placing and accumulating the results, with the layout
#:   work around a kernel;
#: * ``glue`` — everything in a layer's scope outside its executor
#:   (spatial fit, layernorm, activation, attention, concat or residual).
PHASES = WEIGHTS, PATCHES, MACRO, PLACE, GLUE = (
    "weights", "patches", "macro", "place", "glue")

#: Host spans of the program start with this; the benchmark's own start
#: with ``bench.``.
SPAN_PREFIX = "repro."


def span(name: str, **args):
    """A host span ``repro.<name>`` on the profiler's clock, with
    ``args`` as its metadata."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **args)
