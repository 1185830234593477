"""Compiled execution plans (repro/exec): IR contents, compile-time
checks, fused-forward equivalence (bit-identical to the per-layer loop
and to the mapped wrappers), mixed-executor dispatch, plan caching, and
the forced-multi-device shard_map path."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ArrayConfig, MacroGrid, map_net, memo, networks
from repro.cnn.mapped_net import (mapped_net_apply, reference_net_apply,
                                  zero_pruned_kernels)
from repro.exec import (EXECUTORS, compile_plan, execute_layerwise,
                        execute_looped, execute_oracle, execute_plan)

RNG = np.random.RandomState(7)


def _net(name="cnn8", layers=None, grid=MacroGrid(2, 2), groups=(1, 2)):
    layers = networks.NETWORKS[name]() if layers is None else layers
    return map_net(name, layers, ArrayConfig(64, 64), "TetrisG-SDK",
                   grid, groups=groups)


def _data(net, batch=2):
    ks = zero_pruned_kernels(net, [
        jnp.asarray(RNG.randn(m.layer.k_h, m.layer.k_w,
                              m.layer.ic // m.group, m.layer.oc) * 0.1,
                    jnp.float32) for m in net.layers])
    first = net.layers[0].layer
    x = jnp.asarray(RNG.randn(batch, first.ic, first.i_h, first.i_w),
                    jnp.float32)
    return ks, x


def test_compile_plan_ir_fields():
    """The IR records executor, schedule (steps==cycles), glue, carry
    channels, and sharding decisions — all fixed at compile time."""
    net = _net()
    plan = compile_plan(net, executor_policy="mapped")
    assert plan.chained and plan.mesh_axes is None and plan.batch is None
    assert plan.executors == ("mapped",) * len(net.layers)
    assert plan.total_steps == net.total_cycles
    for lp, m in zip(plan.layers, net.layers):
        assert lp.mapping is m
        assert lp.schedule.steps == m.cycles     # compile-time contract
        assert not lp.use_mesh                   # no mesh given
        assert lp.carry_c == m.layer.ic
    assert all(lp.glue.kind == "chain" for lp in plan.layers[:-1])
    assert plan.layers[-1].glue.kind == "last"
    assert f"steps={net.total_cycles} " in plan.describe()


def test_compile_plan_policies():
    """Policy forms: single name, per-layer sequence, callable, auto."""
    net = _net()
    n = len(net.layers)
    assert compile_plan(net, executor_policy="reference").executors == \
        ("reference",) * n
    seq = ["mapped", "reference"] + ["mapped"] * (n - 2)
    assert compile_plan(net, executor_policy=seq).executors == tuple(seq)
    by_ic = compile_plan(
        net, executor_policy=lambda m: "mapped" if m.layer.ic > 32
        else "reference")
    assert set(by_ic.executors) == {"mapped", "reference"}
    auto = compile_plan(net, executor_policy="auto")
    assert all(e in EXECUTORS for e in auto.executors)
    assert "sdk" not in auto.executors       # no TPU in CI
    with pytest.raises(ValueError, match="unknown executor"):
        compile_plan(net, executor_policy="warp")
    with pytest.raises(ValueError, match="lists 2 executors"):
        compile_plan(net, executor_policy=["mapped", "mapped"])


def test_compile_plan_rejects_bad_chain():
    """Chaining errors surface at compile time with the existing
    message, not mid-forward."""
    layers = networks.inception()        # representative set, no chain
    net = _net("inception", layers)
    with pytest.raises(ValueError, match="cannot chain"):
        compile_plan(net, executor_policy="mapped")
    plan = compile_plan(net, executor_policy="mapped", chained=False)
    assert all(lp.glue.kind == "layerwise" for lp in plan.layers)
    ks, _ = _data(net)
    with pytest.raises(ValueError, match="chained plan"):
        execute_plan(plan, ks, jnp.zeros((1, 1, 1, 1)))


def test_compile_plan_sdk_grid_guard():
    """The sdk executor runs passes/groups sequentially: pinning it on a
    mapping that owes a non-degenerate sub-grid must fail at compile."""
    net = _net(grid=MacroGrid(2, 2), groups=(1,))
    assert any(m.sub_grid.p > 1 for m in net.layers)
    with pytest.raises(ValueError, match="not the mapping's .* cycles"):
        compile_plan(net, executor_policy="sdk")


def test_compile_plan_sdk_tiling_rule():
    """The static TPU tiling rule: at a batch whose padded working set
    exceeds the kernel's VMEM cap, ``auto`` on a TPU does not pick sdk,
    an explicit sdk raises at compile, and describe() shows the
    refusal.  Interpret-mode plans (the CPU) are never refused."""
    from repro.exec.plan import _auto_executor
    from repro.kernels.im2win_conv import sdk_refusal
    net = map_net("cnn8", networks.cnn8(), ArrayConfig(512, 512),
                  "TetrisG-SDK", MacroGrid(), groups=(1, 2, 4))
    m = net.layers[1]                        # CNN8-3: sdk-realizable
    assert sdk_refusal(m, 64) is None
    assert _auto_executor(m, backend="tpu", batch=64) == "sdk"
    huge = 4096
    assert "VMEM" in sdk_refusal(m, huge)
    assert _auto_executor(m, backend="tpu", batch=huge) != "sdk"
    pinned = ["mapped", "sdk"] + ["mapped"] * (len(net.layers) - 2)
    with pytest.raises(ValueError, match="tiling rule"):
        compile_plan(net, executor_policy=pinned, batch=huge,
                     interpret=False)
    plan = compile_plan(net, executor_policy="mapped", batch=huge,
                        interpret=False)
    assert ("CNN8-3:mapped[sdk refused: TPU tiling rule: window tiling "
            "needs") in \
        plan.describe()
    assert compile_plan(net, executor_policy=pinned, batch=huge,
                        interpret=True).executors[1] == "sdk"


def test_execute_plan_matches_wrapper_and_loop_cnn8():
    """Acceptance: the fused one-dispatch forward is bit-identical to
    mapped_net_apply (itself the plan wrapper) and to the per-layer
    dispatch loop; oracle agreement at the usual tolerance."""
    net = _net()
    ks, x = _data(net)
    plan = compile_plan(net, executor_policy="mapped")
    y_fused = execute_plan(plan, ks, x)
    assert bool(jnp.all(y_fused == mapped_net_apply(net, ks, x)))
    assert bool(jnp.all(y_fused == execute_looped(plan, ks, x)))
    r = reference_net_apply(net, ks, x)
    np.testing.assert_allclose(
        np.asarray(y_fused), np.asarray(r), rtol=1e-4,
        atol=1e-4 * float(jnp.max(jnp.abs(r))))


def test_execute_plan_matches_wrapper_densenet_slice():
    """Same bit-identity through DenseNet concat glue + marginal-window
    layers, plus gradients: fused vs looped exact, vs oracle at
    reassociation tolerance."""
    net = _net("densenet40", networks.densenet40()[10:15],
               grid=MacroGrid(4, 1))
    ks, x = _data(net, batch=1)
    plan = compile_plan(net, executor_policy="mapped")
    assert any(lp.glue.kind == "concat" for lp in plan.layers)
    y_fused = execute_plan(plan, ks, x)
    assert bool(jnp.all(y_fused == mapped_net_apply(net, ks, x)))
    assert bool(jnp.all(y_fused == execute_looped(plan, ks, x)))

    def loss(fn, k0):
        return jnp.sum(fn(plan, [k0] + list(ks[1:]), x) ** 2)

    gf = jax.grad(lambda k: loss(execute_plan, k))(ks[0])
    gl = jax.grad(lambda k: loss(execute_looped, k))(ks[0])
    assert bool(jnp.all(gf == gl))           # same program modulo fences
    go = jax.grad(lambda k: loss(
        lambda p, kk, xx: execute_oracle(p, kk, xx), k))(ks[0])
    scale = float(jnp.max(jnp.abs(go)))
    assert float(jnp.max(jnp.abs(gf - go))) < 1e-4 * scale


def test_mixed_executor_dispatch():
    """One plan, several executors: reference and mapped layers compose
    in a single fused program and still match the oracle."""
    net = _net()
    n = len(net.layers)
    seq = ["reference" if i % 2 else "mapped" for i in range(n)]
    plan = compile_plan(net, executor_policy=seq)
    ks, x = _data(net)
    y = execute_plan(plan, ks, x)
    r = reference_net_apply(net, ks, x)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(r), rtol=1e-4,
        atol=1e-4 * float(jnp.max(jnp.abs(r))))


def test_mixed_executor_with_sdk_interpret():
    """An sdk (Pallas, interpret mode off-TPU) layer dispatches inside
    the fused program next to the other executors."""
    layers = [networks.cnn8()[0]]
    net = _net("cnn8", layers, grid=MacroGrid(1, 1), groups=(1,))
    plan = compile_plan(net, executor_policy="sdk")
    assert plan.layers[0].interpret          # off-TPU default
    ks, x = _data(net, batch=1)
    y = execute_plan(plan, ks, x)
    r = reference_net_apply(net, ks, x)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(r), rtol=1e-3,
        atol=1e-3 * float(jnp.max(jnp.abs(r))))


def test_execute_layerwise_matches_loop():
    """Layer-set plans: the fused layerwise program equals per-layer
    dispatch on every layer's own input."""
    from repro.exec import apply_layer
    net = _net("inception", networks.inception())
    plan = compile_plan(net, executor_policy="mapped", chained=False)
    ks, _ = _data(net)
    xs = [jnp.asarray(RNG.randn(1, m.layer.ic, m.layer.i_h, m.layer.i_w),
                      jnp.float32) for m in net.layers]
    fused = execute_layerwise(plan, ks, xs)
    for i, y in enumerate(fused):
        assert bool(jnp.all(y == apply_layer(plan, i, xs[i], ks[i])))


def test_plan_memoized():
    """compile_plan joins the memo result cache: the second identical
    compile is a hit, a different policy/batch is a fresh key."""
    memo.clear()
    net = _net()
    p1 = compile_plan(net, executor_policy="mapped")
    misses = memo.stats["result_misses"]
    p2 = compile_plan(net, executor_policy="mapped")
    assert p2 is p1
    assert memo.stats["result_misses"] == misses
    assert memo.stats["result_hits"] >= 1
    compile_plan(net, executor_policy="reference")
    assert memo.stats["result_misses"] == misses + 1


def test_execute_plan_call_checks():
    net = _net()
    ks, x = _data(net)
    plan = compile_plan(net, executor_policy="mapped")
    with pytest.raises(ValueError, match="kernels for"):
        execute_plan(plan, ks[:-1], x)
    with pytest.raises(ValueError, match="channels"):
        execute_plan(plan, ks, x[:, :5])
    batched = compile_plan(net, executor_policy="mapped", batch=4)
    with pytest.raises(ValueError, match="plan batch"):
        execute_plan(batched, ks, x)         # x has batch 2


def test_compile_plan_refuses_ragged_data_batch():
    """A batch that does not divide the mesh's data axis must fail
    loudly at compile (pad first), never silently degrade the whole
    forward to the vmap path."""
    class _FakeMesh:
        axis_names = ("data", "row", "col")
        shape = {"data": 2, "row": 2, "col": 2}
    with pytest.raises(ValueError, match="data axis"):
        compile_plan(_net(), executor_policy="mapped", mesh=_FakeMesh(),
                     batch=3)


def test_plan_shard_map_bit_identical():
    """Tentpole contract on a forced (data=2, row=2, col=2) mesh: the
    fused plan forward is bit-identical to the per-layer loop and agrees
    with the single-device vmap plan to the reassociation rule (DESIGN.md
    §7), with use_mesh resolved at compile time."""
    code = """
import numpy as np, jax, jax.numpy as jnp
from repro.core import ArrayConfig, MacroGrid, map_net, networks
from repro.cnn.mapped_net import zero_pruned_kernels
from repro.exec import (REASSOC_RTOL, compile_plan, execute_looped,
                        execute_plan, reassoc_error)
from repro.launch.mesh import make_serving_mesh
assert len(jax.devices()) == 8
net = map_net("cnn8", networks.cnn8()[:3], ArrayConfig(64, 64),
              "Tetris-SDK", MacroGrid(2, 2))
mesh = make_serving_mesh(2, 2, 4)
assert dict(mesh.shape) == {"data": 2, "row": 2, "col": 2}
plan = compile_plan(net, executor_policy="mapped", mesh=mesh, batch=4)
assert all(lp.use_mesh for lp in plan.layers)
assert plan.mesh_axes == (("data", 2), ("row", 2), ("col", 2))
rng = np.random.RandomState(0)
ks = zero_pruned_kernels(net, [
    jnp.asarray(rng.randn(m.layer.k_h, m.layer.k_w,
                          m.layer.ic // m.group, m.layer.oc) * 0.2,
                jnp.float32) for m in net.layers])
first = net.layers[0].layer
x = jnp.asarray(rng.randn(4, first.ic, first.i_h, first.i_w), jnp.float32)
y_fused = execute_plan(plan, ks, x, mesh=mesh)
y_loop = execute_looped(plan, ks, x, mesh=mesh)
vmap_plan = compile_plan(net, executor_policy="mapped")
y_vmap = execute_plan(vmap_plan, ks, x)
assert bool(jnp.all(y_fused == y_loop)), "fused != loop on mesh"
assert reassoc_error(y_fused, y_vmap) <= REASSOC_RTOL, "sharded != vmap"
try:
    execute_plan(plan, ks, x)                 # mesh omitted: must refuse
except ValueError as e:
    assert "compile mesh" in str(e)
else:
    raise AssertionError("mesh mismatch not caught")
print("PLAN-SHARDED-OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + sys.path))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "PLAN-SHARDED-OK" in out.stdout, out.stderr[-2000:]


def test_plan_lookahead_field_recompiles_once():
    """ISSUE 6 satellite: lookahead is a compiled-plan field (formerly
    the module constant exec.run._LOOKAHEAD).  Each distinct value is a
    distinct plan (own cache key) and retraces the fused program exactly
    once — replays hit the jit cache — and every depth computes the
    same forward."""
    from repro.exec import run as exec_run
    # a (layers, grid) combination no other test executes fused, so the
    # jit cache holds no prior trace of these plans
    net = _net("cnn8", networks.cnn8()[:2], grid=MacroGrid(1, 1),
               groups=(1,))
    ks, x = _data(net, batch=3)
    plans = {la: compile_plan(net, executor_policy="reference",
                              lookahead=la) for la in (0, 1, 2)}
    for la, p in plans.items():
        assert p.lookahead == la
        assert f"lookahead={la}" in p.describe()
    assert len({id(p) for p in plans.values()}) == 3   # distinct keys
    base = exec_run.fused_trace_count
    ys = []
    for p in plans.values():
        y0 = execute_plan(p, ks, x)
        y1 = execute_plan(p, ks, x)          # replay: no retrace
        assert bool(jnp.all(y0 == y1))
        ys.append(y0)
    assert exec_run.fused_trace_count == base + 3  # one per depth
    for y in ys[1:]:                 # fences reorder nothing observable
        assert bool(jnp.all(y == ys[0]))
    # default plans keep lookahead=1 and memoize as before
    assert compile_plan(net, executor_policy="reference").lookahead == 1
    assert compile_plan(net, executor_policy="reference",
                        lookahead=1) is \
        compile_plan(net, executor_policy="reference")
    with pytest.raises(ValueError, match="lookahead"):
        compile_plan(net, executor_policy="reference", lookahead=-1)


def test_plan_vmem_budget_param_and_env(monkeypatch):
    """ISSUE 6 satellite: the sdk block="auto" VMEM budget is an
    explicit byte parameter with the REPRO_SDK_VMEM_BUDGET env var as
    the deploy-time default — resolved at compile, recorded in the IR,
    and part of the plan cache key."""
    from repro.kernels.im2win_conv import (DEFAULT_VMEM_BUDGET,
                                           default_vmem_budget)
    net = _net()
    monkeypatch.delenv("REPRO_SDK_VMEM_BUDGET", raising=False)
    assert default_vmem_budget() == DEFAULT_VMEM_BUDGET
    p_def = compile_plan(net, executor_policy="mapped")
    assert all(lp.vmem_budget == DEFAULT_VMEM_BUDGET for lp in p_def.layers)
    p_exp = compile_plan(net, executor_policy="mapped",
                         vmem_budget=1 << 20)
    assert all(lp.vmem_budget == 1 << 20 for lp in p_exp.layers)
    assert p_exp is not p_def                # distinct cache key
    # env default: None resolves through the env var, landing on the
    # SAME cache key as the explicit byte count
    monkeypatch.setenv("REPRO_SDK_VMEM_BUDGET", str(1 << 20))
    assert default_vmem_budget() == 1 << 20
    assert compile_plan(net, executor_policy="mapped") is p_exp
    monkeypatch.setenv("REPRO_SDK_VMEM_BUDGET", "8M")
    with pytest.raises(ValueError, match="not an integer"):
        default_vmem_budget()
    monkeypatch.setenv("REPRO_SDK_VMEM_BUDGET", "-4")
    with pytest.raises(ValueError, match="must be > 0"):
        default_vmem_budget()


# ---------------------------------------------------------------------------
# Inception modules: branch-and-concat glue through the one plan path
# ---------------------------------------------------------------------------

#: Two Inception modules at widths 4-16 with a 3x3/2 stage pool (ceil
#: mode, 8 -> 4) before the second: the published module at toy scale.
TOY_MODULES = (("a", 8, 8, 4, 4, 8, 4, 4, 4),
               ("b", 20, 4, 8, 4, 8, 4, 8, 4))


def _inception_toy(modules=TOY_MODULES, edit=None):
    """The toy's mapping with its glue; ``edit(layers, glue)`` may change
    both (lists) before mapping."""
    import dataclasses
    layers, glue = networks.inception_modules(modules, ("b",))
    layers, glue = list(layers), list(glue)
    if edit is not None:
        edit(layers, glue)
    net = _net("inception_toy", layers, groups=(1, 2, 4))
    return dataclasses.replace(net, glue=tuple(glue))


def _bench_googlenet_reference():
    """bench/references/googlenet.py, loaded as the benchmark loads it
    (it imports bench/reference.py by its top-level name)."""
    import importlib.util
    from pathlib import Path
    bench = Path(__file__).resolve().parents[1] / "bench"
    if str(bench) not in sys.path:
        sys.path.append(str(bench))
    spec = importlib.util.spec_from_file_location(
        "bench_reference_googlenet", bench / "references" / "googlenet.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_inception_toy_matches_oracle_and_bench_reference():
    """The fused plan, the per-layer loop, `execute_oracle` and the
    benchmark's independent reference agree.  Tolerance as the other
    plan-vs-oracle tests: rtol 1e-4 with atol scaled to max|ref|, since
    the mapped executor sums each convolution's terms over array passes
    in another order than lax.conv (float32 reassociation, not a
    semantic difference: a lost branch or pool is O(1) off)."""
    net = _inception_toy()
    plan = compile_plan(net, executor_policy="mapped", batch=2)
    assert [lp.glue.kind for lp in plan.layers] == \
        ["branch", "chain", "branch", "chain", "branch", "join"] * 2
    assert [lp.carry_c for lp in plan.layers] == \
        [lp.mapping.layer.ic for lp in plan.layers]
    ks, x = _data(net)
    y = execute_plan(plan, ks, x)
    assert y.shape == (2, 28, 4, 4)
    r = execute_oracle(plan, ks, x)
    tol = dict(rtol=1e-4, atol=1e-4 * float(jnp.max(jnp.abs(r))))
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), **tol)
    np.testing.assert_allclose(np.asarray(execute_looped(plan, ks, x)),
                               np.asarray(r), **tol)
    ref = _bench_googlenet_reference()
    config = {"layers": [
        {"name": m.layer.name, "size": m.layer.i_h, "kernel": m.layer.k_h,
         "ic": m.layer.ic, "oc": m.layer.oc, "stride": m.layer.stride,
         "groups": m.group} for m in net.layers]}
    want = ref.forward(config, ks, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4,
                               atol=1e-4 * float(jnp.max(jnp.abs(want))))


def test_inception_glue_bytes_per_image_hand_count():
    """`NetworkPlan.glue_bytes_per_image` counts every glue write from
    the static shapes, per image, in float32."""
    plan = compile_plan(_inception_toy(), executor_policy="mapped")
    module_a = (4 * 10 * 8 + 4 * 10 * 10    # 3x3: pad H, then W
                + 4 * 12 * 8 + 4 * 12 * 12  # 5x5: pad H, then W
                + 8 * 8 * 8                 # pool proj's 3x3/1 pool
                + 20 * 8 * 8)               # join: 4 + 8 + 4 + 4
    module_b = (20 * 4 * 4                  # 3x3/2 stage pool, 8 -> 4
                + 4 * 6 * 4 + 4 * 6 * 6
                + 4 * 8 * 4 + 4 * 8 * 8
                + 20 * 4 * 4
                + 28 * 4 * 4)               # join: 8 + 8 + 8 + 4
    assert plan.glue_bytes_per_image == 4 * (module_a + module_b)
    assert f" glue_bytes={4 * (module_a + module_b)} " in plan.describe()
    # a chain whose sizes line up writes nothing between its layers
    one = compile_plan(_net(layers=networks.cnn8()[:1]),
                       executor_policy="mapped")
    assert one.glue_bytes_per_image == 0


def _glue_edit(i, **kw):
    import dataclasses

    def edit(layers, glue):
        glue[i] = dataclasses.replace(glue[i], **kw)
    return edit


def _layer_edit(i, **kw):
    import dataclasses

    def edit(layers, glue):
        layers[i] = dataclasses.replace(layers[i], **kw)
    return edit


def _drop_last(layers, glue):
    del layers[-1], glue[-1]


@pytest.mark.parametrize("edit,match", [
    (_glue_edit(0, save=False), "kind='branch' with no saved module input"),
    (_glue_edit(0, kind="join"), "kind='join' with no open branches"),
    (_layer_edit(1, ic=4), r"cannot chain Ga-1x1 \(branch, 8 carry "
                           r"channels\) into Ga-3x3r \(ic=4\)"),
    (_layer_edit(2, i_w=12, i_h=12), r"join concatenates branch outputs "
                                     r"of different sizes"),
    (_drop_last, "module is left open at the end"),
], ids=["saved_with_empty_stack", "join_without_branches",
        "branch_channels", "join_sizes", "module_left_open"])
def test_inception_glue_refused_at_compile(edit, match):
    net = _inception_toy(edit=edit)
    with pytest.raises(ValueError, match=match):
        compile_plan(net, executor_policy="mapped")


def test_inception_join_channels_refused_at_compile():
    """A join whose concatenated widths (4 + 8 + 4 + 4) do not match the
    next module's input channels."""
    mods = (TOY_MODULES[0], ("b", 24) + TOY_MODULES[1][2:])
    with pytest.raises(ValueError, match=r"cannot chain Ga-pool \(join, 20 "
                                         r"carry channels\) into Gb-1x1 "
                                         r"\(ic=24\)"):
        compile_plan(_inception_toy(mods), executor_policy="mapped")


def test_googlenet_program_entry_plans_whole():
    """`networks.googlenet` with its glue, as `serve_cnn` maps it: the
    nine modules plan whole (no chainable-prefix cut), with a join
    closing each module and the concat widths of Table 1."""
    from repro.core import ArrayConfig as AC
    from repro.launch.fleet import chainable_prefix
    from repro.launch.serve_cnn import map_for_serving
    net, _ = map_for_serving("googlenet", AC(512, 512), "TetrisG-SDK")
    assert net.glue == networks.googlenet_glue()
    assert chainable_prefix(net) is net
    plan = compile_plan(net, executor_policy="mapped", batch=1)
    assert len(plan.layers) == 54
    joins = [i for i, lp in enumerate(plan.layers) if lp.glue.kind == "join"]
    assert joins == list(range(5, 54, 6))
    widths = [sum(r[3:]) - r[4] - r[6] for r in networks.GOOGLENET_MODULES]
    assert widths == [256, 480, 512, 512, 512, 528, 832, 832, 1024]
    assert [plan.layers[j + 1].carry_c for j in joins[:-1]] == widths[:-1]
    stage = [lp.mapping.layer.name for lp in plan.layers
             if lp.glue.pool == "max3s2"]
    assert stage == ["G4a-1x1", "G5a-1x1"]


def test_with_glue_attaches_only_named_graphs():
    """`networks.with_glue` leaves a chain's mapping as it is (its glue
    is inferred) and gives a googlenet prefix the glue of its own
    layers: the first two modules then plan whole, closed by a join."""
    from repro.core import ArrayConfig as AC
    cnn8 = _net()
    assert networks.with_glue("cnn8", cnn8) is cnn8
    head = map_net("googlenet", networks.googlenet()[:12], AC(512, 512),
                   "TetrisG-SDK", MacroGrid(1, 1), groups=(1, 2, 4))
    net = networks.with_glue("googlenet", head)
    assert net.glue == networks.googlenet_glue()[:12]
    plan = compile_plan(net, executor_policy="mapped")
    assert [lp.glue.kind for lp in plan.layers] == \
        ["branch", "chain", "branch", "chain", "branch", "join"] * 2
    assert [lp.carry_c for lp in plan.layers] == \
        [192, 192, 96, 192, 16, 192, 256, 256, 128, 256, 32, 256]
