"""The names the program gives its work: a named scope per planned layer
and per executor phase in the compiled program's metadata, and the
program's host spans in a profiler trace."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ArrayConfig, MacroGrid, map_net, networks
from repro.exec import compile_plan, execute_plan
from repro.exec.run import _execute_jit
from repro.launch.batching import Coalescer, PlanLadder
from repro.launch.tracing import PHASES, SPAN_PREFIX

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_LAYER = re.compile(r"(?:^|/)(L\d\d\.[^/]+)/")


def _mapped(net: str):
    return map_net(net, networks.NETWORKS[net](), ArrayConfig(512, 512),
                   "TetrisG-SDK", MacroGrid(), groups=(1, 2, 4))


@pytest.mark.parametrize("net", ["cnn8", "densenet40"])
def test_compiled_plan_names_every_layer_and_phase(net):
    """Lowered and compiled at batch 8 under ``mapped``: every planned
    layer carries its ``L{i:02d}.`` scope in the compiled HLO's op
    names, and each executor phase and the glue appear under them."""
    nm = _mapped(net)
    plan = compile_plan(nm, executor_policy="mapped", batch=8)
    ks = tuple(jax.ShapeDtypeStruct(
        (m.layer.k_h, m.layer.k_w, m.layer.ic // m.group, m.layer.oc),
        jnp.float32) for m in nm.layers)
    lay0 = nm.layers[0].layer
    x = jax.ShapeDtypeStruct((8, lay0.ic, lay0.i_h, lay0.i_w), jnp.float32)
    # an instruction XLA merged from several names them all, ";"-joined
    names = [n for joined in _OP_NAME.findall(
        _execute_jit.lower(plan, ks, x).compile().as_text())
        for n in joined.split(";")]
    scoped = [n for n in names if _LAYER.search(n)]
    want = [f"L{i:02d}.{m.layer.name}" for i, m in enumerate(nm.layers)]
    assert sorted({_LAYER.search(n).group(1) for n in scoped}) == want
    phases = {p for n in scoped for p in n.split("/") if p in PHASES}
    assert phases == set(PHASES)
    # a phase never nests inside another
    for n in scoped:
        assert sum(p in PHASES for p in n.split("/")) <= 1, n


def test_program_spans_reach_the_profiler(tmp_path):
    """``repro.pop``, ``repro.plan_for`` and ``repro.execute_plan`` (with
    its tier) land on the profiler's host plane, the dispatch inside
    the span that wraps it."""
    from jax.profiler import ProfileData
    nm = _mapped("cnn8")
    ladder = PlanLadder(nm, (1, 2))
    rng = np.random.RandomState(0)
    ks = [jnp.asarray(rng.randn(m.layer.k_h, m.layer.k_w,
                                m.layer.ic // m.group, m.layer.oc) * 0.1,
                      jnp.float32) for m in nm.layers]
    lay0 = nm.layers[0].layer
    x = jnp.zeros((2, lay0.ic, lay0.i_h, lay0.i_w), jnp.float32)
    execute_plan(ladder.plans[2], ks, x).block_until_ready()   # compile
    co = Coalescer(max_batch=2, max_delay_s=0.0)
    co.push(2, now=0.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("outer"):
            batch = co.pop(now=1.0)
            tier, plan = ladder.plan_for(sum(r.rows for r in batch))
            execute_plan(plan, ks, x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    events = [(e.name, e.start_ns, e.end_ns)
              for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    spans = {n: (s, e) for n, s, e in events if n.startswith(SPAN_PREFIX)
             or n == "outer"}
    assert {"repro.pop", "repro.plan_for", "repro.execute_plan"} <= set(
        spans)
    lo, hi = spans["outer"]
    assert all(lo <= s <= e <= hi for s, e in spans.values())
    assert spans["repro.pop"][1] <= spans["repro.plan_for"][0]
    assert spans["repro.plan_for"][1] <= spans["repro.execute_plan"][0]
    assert tier == 2
