"""Memory-model + segmentation passes (exec/memory.py, exec/remat.py)
and the remat'd fused forward: IR-derived byte estimates, the
concat-groups-never-split boundary rule, greedy budgeting, the
PLAN_VERSION stale-cache contract, and remat on/off forward
bit-identity + exact gradients through `jax.checkpoint` segments."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ArrayConfig, MacroGrid, map_net, memo, networks
from repro.cnn.mapped_net import zero_pruned_kernels
from repro.exec import (REASSOC_RTOL, compile_plan, execute_plan,
                        reassoc_error)
from repro.exec.memory import (ITEMSIZE, LayerMemory, activation_bytes,
                               peak_bytes, total_bytes, weight_prep_bytes)
from repro.exec.remat import (allowed_cuts, canonical_remat,
                              greedy_segments, plan_segments)

RNG = np.random.RandomState(11)


def _net(name="cnn8", layers=None):
    layers = networks.NETWORKS[name]() if layers is None else layers
    return map_net(name, layers, ArrayConfig(64, 64), "TetrisG-SDK",
                   MacroGrid(2, 2), groups=(1, 2))


def _densenet_prefix(n=14):
    """densenet40 block 1 + its 1x1 transition (index 12) + the start
    of block 2 — the smallest slice with a legal cut inside it."""
    return _net("densenet40_p", networks.densenet40()[:n])


def _data(net, batch=2):
    ks = zero_pruned_kernels(net, [
        jnp.asarray(RNG.randn(m.layer.k_h, m.layer.k_w,
                              m.layer.ic // m.group, m.layer.oc) * 0.1,
                    jnp.float32) for m in net.layers])
    first = net.layers[0].layer
    x = jnp.asarray(RNG.randn(batch, first.ic, first.i_h, first.i_w),
                    jnp.float32)
    return ks, x


# ---------------------------------------------------------------- model

def test_layer_memory_matches_formulas():
    """The memory pass writes per-layer estimates into the IR: act bytes
    are the carry activation entering the layer at the plan batch, and
    the plan's unremat peak is their plain sum."""
    net = _net()
    plan = compile_plan(net, executor_policy="mapped", batch=2)
    for lp in plan.layers:
        lay = lp.mapping.layer
        assert lp.act_bytes == 2 * lp.carry_c * lay.i_h * lay.i_w * ITEMSIZE
        assert lp.act_bytes == activation_bytes(lp.mapping, lp.carry_c, 2)
        assert lp.weight_bytes == weight_prep_bytes(lp.mapping) > 0
        assert lp.mem_bytes == lp.act_bytes + lp.weight_bytes
    assert plan.unremat_peak_bytes == sum(lp.mem_bytes
                                          for lp in plan.layers)
    # no batch given -> estimates price a single example
    b1 = compile_plan(net, executor_policy="mapped")
    assert b1.layers[0].act_bytes == plan.layers[0].act_bytes // 2


def test_peak_model():
    """peak = heaviest segment + stored boundary carries; one segment
    degenerates to the total."""
    mem = [LayerMemory(f"l{i}", act_bytes=10, weight_bytes=5)
           for i in range(4)]
    assert total_bytes(mem) == 60
    assert peak_bytes(mem, [(0, 4)]) == 60
    # two segments of 2: heaviest 30, one boundary carry of 10
    assert peak_bytes(mem, [(0, 2), (2, 4)]) == 40
    assert peak_bytes(mem, [(0, 1), (1, 4)]) == 45 + 10


def test_describe_surfaces_memory():
    net = _densenet_prefix()
    plan = compile_plan(net, executor_policy="mapped", batch=2,
                        remat=(12,))
    assert "peak_mem=" in plan.describe()
    assert "segments=2" in plan.describe()
    txt = plan.describe_memory()
    assert txt.count("act=") == len(plan.layers)
    assert "<- segment" in txt
    flat = compile_plan(net, executor_policy="mapped", batch=2)
    assert "segments=" not in flat.describe()      # PR-4-era shape


# ----------------------------------------------------------- boundaries

def test_allowed_cuts_chain_every_boundary():
    net = _net()
    plan = compile_plan(net, executor_policy="mapped")
    glue = [lp.glue for lp in plan.layers]
    assert allowed_cuts(glue) == tuple(range(len(net.layers) - 1))


def test_allowed_cuts_densenet_transitions_only():
    """Inside a dense block every output is saved for downstream concats
    — the only legal cuts are the 1x1 transitions (full net: 12, 25)."""
    net = _densenet_prefix()
    plan = compile_plan(net, executor_policy="mapped")
    glue = [lp.glue for lp in plan.layers]
    assert allowed_cuts(glue) == (12,)


def test_explicit_cuts_never_split_concat_groups():
    """Property form of the never-split rule: EVERY non-transition
    boundary of the densenet prefix is rejected with the allowed list
    in the message; the transition itself compiles to two segments."""
    net = _densenet_prefix()
    for bad in range(12):
        with pytest.raises(ValueError, match="illegal remat boundaries"):
            compile_plan(net, executor_policy="mapped", batch=2,
                         remat=(bad,))
    plan = compile_plan(net, executor_policy="mapped", batch=2,
                        remat=(12,))
    assert plan.segments == ((0, 13), (13, len(net.layers)))
    assert plan.peak_bytes < plan.unremat_peak_bytes


def test_greedy_segments_budget_behavior():
    mem = [LayerMemory(f"l{i}", act_bytes=8, weight_bytes=2)
           for i in range(6)]
    allowed = tuple(range(5))
    assert greedy_segments(mem, allowed, total_bytes(mem)) == ((0, 6),)
    # tiny budget: every allowed boundary cuts
    segs = greedy_segments(mem, allowed, 1)
    assert segs == ((0, 1), (1, 2), (2, 3), (3, 4), (4, 5), (5, 6))
    # segments always tile the layer range contiguously
    for budget in (15, 25, 40):
        segs = greedy_segments(mem, allowed, budget)
        assert segs[0][0] == 0 and segs[-1][1] == 6
        assert all(a[1] == b[0] for a, b in zip(segs, segs[1:]))
    # restricted legality: greedy only uses the cuts it is given
    segs = greedy_segments(mem, (3,), 1)
    assert segs == ((0, 4), (4, 6))


def test_plan_segments_spec_forms():
    mem = [LayerMemory(f"l{i}", act_bytes=8, weight_bytes=2)
           for i in range(6)]
    allowed = tuple(range(5))
    assert plan_segments(mem, allowed, None) is None
    assert plan_segments(mem, allowed, ("cuts", (2,))) == ((0, 3), (3, 6))
    assert plan_segments(mem, allowed, ("budget", 30)) == \
        greedy_segments(mem, allowed, 30)
    # auto with no env budget targets ~sqrt(n) segments
    auto = plan_segments(mem, allowed, ("auto", None))
    assert len(auto) >= 2


def test_canonical_remat_forms(monkeypatch):
    monkeypatch.delenv("REPRO_TRAIN_MEM_BUDGET", raising=False)
    assert canonical_remat(None) is None
    assert canonical_remat("off") is None
    assert canonical_remat(False) is None
    assert canonical_remat("auto") == ("auto", None)
    monkeypatch.setenv("REPRO_TRAIN_MEM_BUDGET", "12345")
    assert canonical_remat("auto") == ("auto", 12345)
    assert canonical_remat(1 << 20) == ("budget", 1 << 20)
    assert canonical_remat([3, 1]) == ("cuts", (1, 3))
    with pytest.raises(ValueError, match="positive"):
        canonical_remat(0)
    with pytest.raises(ValueError, match="ambiguous"):
        canonical_remat(True)
    with pytest.raises(ValueError):
        canonical_remat(object())


# ------------------------------------------------------------ execution

def test_remat_forward_and_grads_exact_chain():
    """cnn8 (plain chain): the segmented program is the SAME math —
    forward bit-identical, gradients exactly equal.  Reference executor:
    the property is segment-structural, and mapped-vs-reference gradient
    equality is already pinned by tests/test_mapped_net.py."""
    net = _net()
    ks, x = _data(net)
    flat = compile_plan(net, executor_policy="reference", batch=2)
    seg = compile_plan(net, executor_policy="reference", batch=2,
                       remat="auto")
    assert len(seg.spans) > 1

    def loss(plan):
        return lambda ks: execute_plan(plan, ks, x).sum()

    y0, y1 = execute_plan(flat, ks, x), execute_plan(seg, ks, x)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    g0 = jax.grad(loss(flat))(ks)
    g1 = jax.grad(loss(seg))(ks)
    for a, b in zip(g0, g1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_remat_forward_and_grads_exact_densenet_concat():
    """DenseNet prefix (concat glue + a transition cut): checkpointing
    at the transition must not perturb the forward (bit-identical), and
    the gradients agree to the reassociation rule (DESIGN.md §7): the
    recomputed segment's backward is a separately fused program."""
    net = _densenet_prefix()
    ks, x = _data(net)
    flat = compile_plan(net, executor_policy="reference", batch=2)
    seg = compile_plan(net, executor_policy="reference", batch=2,
                       remat=(12,))

    def loss(plan):
        return lambda ks: execute_plan(plan, ks, x,
                                       activation=jax.nn.relu).sum()

    y0 = execute_plan(flat, ks, x, activation=jax.nn.relu)
    y1 = execute_plan(seg, ks, x, activation=jax.nn.relu)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    g0 = jax.grad(loss(flat))(ks)
    g1 = jax.grad(loss(seg))(ks)
    for a, b in zip(g0, g1):
        assert reassoc_error(b, a) <= REASSOC_RTOL, reassoc_error(b, a)


# -------------------------------------------------------------- caching

@pytest.fixture
def disk_cache(tmp_path):
    memo.clear()
    memo.set_disk_cache(tmp_path)
    try:
        yield tmp_path
    finally:
        memo.set_disk_cache(None)
        memo.clear()


def test_plan_version_stale_cache(disk_cache, monkeypatch):
    """The PLAN_VERSION bump contract: plans persist under their
    version, so a payload written by an older schema reads as a miss
    (recompile), never as a stale value."""
    net = _net()
    compile_plan(net, executor_policy="mapped", batch=2, remat="auto")
    memo.clear()
    h0 = memo.stats["disk_hits"]
    compile_plan(net, executor_policy="mapped", batch=2, remat="auto")
    assert memo.stats["disk_hits"] > h0          # warm across processes
    # a version bump must ignore every previously persisted plan
    monkeypatch.setattr(memo, "PLAN_VERSION", memo.PLAN_VERSION + 1)
    memo.clear()
    h1, m1 = memo.stats["disk_hits"], memo.stats["disk_misses"]
    plan = compile_plan(net, executor_policy="mapped", batch=2,
                        remat="auto")
    assert memo.stats["disk_hits"] == h1         # no stale read
    assert memo.stats["disk_misses"] > m1
    assert plan.segments is not None             # recompiled for real


def test_env_budget_part_of_cache_key(monkeypatch):
    """Flipping REPRO_TRAIN_MEM_BUDGET must never serve a stale "auto"
    plan: the env budget folds into the canonical spec and the key."""
    net = _densenet_prefix()
    monkeypatch.delenv("REPRO_TRAIN_MEM_BUDGET", raising=False)
    a = compile_plan(net, executor_policy="mapped", batch=2, remat="auto")
    monkeypatch.setenv("REPRO_TRAIN_MEM_BUDGET",
                       str(net.layers[0].layer.i_w))   # absurdly tiny
    b = compile_plan(net, executor_policy="mapped", batch=2, remat="auto")
    assert a is not b
    assert len(b.spans) >= len(a.spans)


# ------------------------------------------------------- inception modules

def _inception_toy():
    """Two Inception modules (widths 4-16, a 3x3/2 stage pool between
    them) with their branch glue."""
    import dataclasses
    layers, glue = networks.inception_modules(
        (("a", 8, 8, 4, 4, 8, 4, 4, 4), ("b", 20, 4, 8, 4, 8, 4, 8, 4)),
        ("b",))
    return dataclasses.replace(_net("inception_toy", layers), glue=glue)


def test_allowed_cuts_never_fall_inside_a_module():
    """Cuts only after a join: inside a module the saved input and the
    open branches are live (and the last layer is never a cut)."""
    plan = compile_plan(_inception_toy(), executor_policy="mapped")
    assert allowed_cuts([lp.glue for lp in plan.layers]) == (5,)
    assert allowed_cuts(networks.googlenet_glue()) == tuple(range(5, 53, 6))
    with pytest.raises(ValueError, match="illegal remat boundaries"):
        compile_plan(_inception_toy(), executor_policy="mapped", remat=[2])


def test_inception_layer_memory_matches_formulas():
    """Inside a module the memory pass prices each layer by its own
    input, as for a chain: a layer after a branch by the module input
    it reads, the 3x3 and 5x5 by their reduces' outputs."""
    plan = compile_plan(_inception_toy(), executor_policy="mapped",
                        batch=2)
    assert [lp.carry_c for lp in plan.layers] == \
        [8, 8, 4, 8, 4, 8, 20, 20, 4, 20, 4, 20]
    for lp in plan.layers:
        lay = lp.mapping.layer
        assert lp.act_bytes == 2 * lp.carry_c * lay.i_h * lay.i_w * ITEMSIZE
        assert lp.mem_bytes == lp.act_bytes + lp.weight_bytes
    assert plan.unremat_peak_bytes == sum(lp.mem_bytes for lp in plan.layers)


def test_remat_at_module_boundary_forward_unchanged():
    """Segments cut between modules run each module inside one
    `jax.checkpoint` body: the forward is what the unsegmented plan
    computes."""
    net = _inception_toy()
    flat = compile_plan(net, executor_policy="mapped", batch=2)
    seg = compile_plan(net, executor_policy="mapped", batch=2, remat=[5])
    assert seg.spans == ((0, 6), (6, 12))
    ks, x = _data(net)
    assert reassoc_error(execute_plan(seg, ks, x),
                         execute_plan(flat, ks, x)) <= REASSOC_RTOL


def test_train_plan_refuses_branch_glue():
    from repro.cnn.train import train_plan
    with pytest.raises(ValueError, match="branch glue"):
        train_plan(_inception_toy(), steps=1, batch=2)
