"""A configuration brings its own reference and its layer graph as
files, and the harness hands the graph to the program.

On the CPU: the committed toy ``data/residual_toy.json`` (a bottleneck
residual block between two 3x3 convs, which the program's inferred
chain/concat glue cannot express) served end to end by ``run.run_cell``
against its own reference ``data/residual_toy_reference.py``; and the
glue the program infers for cnn8 and densenet40, written out as
``"glue"`` entries, planning and computing what the inferred glue does.
"""
import copy
import dataclasses
import json
import time
from pathlib import Path

import numpy as np
import pytest

import cells
import harness
import reference
import run

DATA = Path(__file__).resolve().parent / "data"
SMALL_MIX = {"arrivals": "poisson", "pool_images": 64, "max_batch": 4,
             "max_delay_ms": 2.0,
             "sizes": {"zipf_exponent": 1.5, "min": 1, "max": 4}}
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def _toy() -> dict:
    with open(DATA / "residual_toy.json") as f:
        return json.load(f)


def _cell(config: dict) -> cells.Cell:
    e2e = [m for m in cells.load_spec()["end_to_end"]
           if m["name"] in ("latency_p95_ms", "setup_s")]
    return cells.Cell(name="residual_toy.poisson", chips=1, config=config,
                      traffic=SMALL_MIX, fixed={"rate_per_s": 150.0},
                      end_to_end=tuple(e2e), per_layer=())


def _run(config: dict, seed: int = 2**33 + 5) -> dict:
    return run.run_cell(_cell(config), seed=seed, seconds=1.0, trace=False,
                        t_start=time.perf_counter(), device=DEVICE)


def _kinds(plan):
    return [(lp.glue.kind, lp.glue.save) for lp in plan.layers]


def test_residual_toy_is_served_correct():
    from repro.exec import compile_plan
    cfg = _toy()
    net = harness.build_mapping(cfg)
    assert _kinds(compile_plan(net, executor_policy="mapped", batch=2)) == [
        ("chain", False), ("chain", True), ("residual", False),
        ("last", False)]
    res = _run(cfg)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0
    assert res["checks"]["worst_rel_err"]["value"] < 1e-4


def test_residual_toy_reference_adds_the_saved_input():
    """The toy's reference against the same graph written out in NumPy
    (float64, 1x1 convs as einsums)."""
    cfg = _toy()
    mod = cells.reference_module(cfg)
    kernels, pool = mod.make_data(cfg, 3, seed=4)
    k = [np.asarray(w, np.float64) for w in kernels]
    x = np.asarray(pool, np.float64)

    def conv3(x, w):
        o = x.shape[2] - 2
        return sum(np.einsum("bchw,co->bohw", x[:, :, i:i + o, j:j + o],
                             w[i, j]) for i in range(3) for j in range(3))

    a = conv3(x, k[0])
    b = np.einsum("bchw,co->bohw", a, k[1][0, 0])
    c = a + np.einsum("bchw,co->bohw", b, k[2][0, 0])
    want = conv3(c, k[3])
    got = mod.forward_blocks(cfg, kernels, np.asarray(pool), block=2)
    assert got.shape == (3, 8, 8, 8)
    assert reference.rel_err(got, want) < 1e-5


@pytest.mark.parametrize("lacking", [["RT-r2"], ["RT-c1", "RT-r1", "RT-c2"]],
                         ids=["one", "all_but_one"])
def test_glue_on_some_layers_only_is_refused_at_setup(lacking):
    cfg = _toy()
    for lay in cfg["layers"]:
        if lay["name"] in lacking:
            del lay["glue"]
    with pytest.raises(ValueError, match="give every layer its glue") as e:
        harness.Server(cfg, SMALL_MIX, seed=1)
    assert str(lacking) in str(e.value)


def test_residual_toy_without_its_glue_is_planned_as_a_chain_and_fails(
        monkeypatch):
    """The inferred rule takes the toy's channel counts as a plain chain
    (a residual keeps a chain's channel count, so no chaining error can
    come): with the glue lost on its way to the program, the run is not
    correct."""
    from repro.exec import compile_plan
    cfg = _toy()
    bare = copy.deepcopy(cfg)
    for lay in bare["layers"]:
        del lay["glue"]
    net = harness.build_mapping(bare)
    assert net.glue is None
    assert _kinds(compile_plan(net, executor_policy="mapped", batch=2)) == [
        ("chain", False), ("chain", False), ("chain", False),
        ("last", False)]
    build = harness.build_mapping
    monkeypatch.setattr(harness, "build_mapping",
                        lambda c: dataclasses.replace(build(c), glue=None))
    res = _run(cfg)
    assert res["correct"] is False
    chk = res["checks"]["worst_rel_err"]
    assert chk["value"] > chk["limit"]


@pytest.mark.parametrize("name", ["cnn8", "densenet40"])
def test_explicit_glue_equal_to_the_inferred_changes_nothing(name):
    import jax
    from repro.exec import execute_plan
    from repro.launch.batching import PlanLadder
    cfg = cells.load_config(name)
    net = harness.build_mapping(cfg)
    assert net.glue is None
    inferred = PlanLadder(net, (2,)).plans[2]
    written = copy.deepcopy(cfg)
    for lay, lp in zip(written["layers"], inferred.layers):
        lay["glue"] = json.loads(json.dumps(dataclasses.asdict(lp.glue)))
    net_x = harness.build_mapping(written)
    assert net_x.glue is not None
    assert dataclasses.replace(net_x, glue=None) == net
    explicit = PlanLadder(net_x, (2,)).plans[2]
    assert [lp.glue for lp in explicit.layers] == \
        [lp.glue for lp in inferred.layers]
    assert [lp.carry_c for lp in explicit.layers] == \
        [lp.carry_c for lp in inferred.layers]
    if name == "cnn8":
        kernels, pool = reference.make_data(cfg, 2, seed=2**32 + 9)
        y0 = execute_plan(inferred, kernels, jax.device_put(pool))
        y1 = execute_plan(explicit, kernels, jax.device_put(pool))
        assert np.array_equal(np.asarray(y0), np.asarray(y1))


@pytest.mark.parametrize("name", ["cnn8", "densenet40"])
def test_default_reference_is_bench_reference(name):
    """A configuration without ``"reference"`` gets ``bench/reference.py``:
    the same weights and images (compared for cnn8, whose weights the CPU
    makes in seconds), FLOPs and mapping as before the key existed."""
    cfg = cells.load_config(name)
    mod = cells.reference_module(cfg)
    assert Path(mod.__file__) == Path(reference.__file__)
    assert mod.useful_flops_per_image(cfg) == \
        reference.useful_flops_per_image(cfg)
    assert harness.layer_glue(cfg) is None
    if name == "cnn8":
        k1, p1 = mod.make_data(cfg, 8, 2**31 + 77)
        k2, p2 = reference.make_data(cfg, 8, 2**31 + 77)
        assert all(np.array_equal(a, b) for a, b in zip(k1, k2))
        assert np.array_equal(p1, p2)
