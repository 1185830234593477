"""googlenet: GoogLeNet's nine Inception modules as files.

On the CPU: the configuration's 54 layers against Table 1 of Szegedy et
al. 2014, its mapping and FLOPs, the committed two-module toy
``data/inception_toy.json`` (branch-and-concat glue and a ceil-mode
stage pool) served end to end by ``run.run_cell`` against
``bench/references/googlenet.py``, and the ``glue_bytes_per_image``
reader.
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


def _cell(config: dict) -> cells.Cell:
    e2e = [m for m in cells.load_spec()["end_to_end"]
           if m["name"] in ("latency_p95_ms", "setup_s")]
    return cells.Cell(name="inception_toy.poisson", chips=1, config=config,
                      traffic=SMALL_MIX, fixed={"rate_per_s": 150.0},
                      end_to_end=tuple(e2e), per_layer=())


def _run(config: dict, seed: int = 2**33 + 5) -> dict:
    return run.run_cell(_cell(config), seed=seed, seconds=1.0, trace=False,
                        t_start=time.perf_counter(), device=DEVICE)


#: Szegedy et al. 2014, Table 1, rows inception(3a)-(5b): module, input
#: channels, feature size, #1x1, #3x3 reduce, #3x3, #5x5 reduce, #5x5,
#: pool proj.
TABLE_1 = [("3a", 192, 28, 64, 96, 128, 16, 32, 32),
           ("3b", 256, 28, 128, 128, 192, 32, 96, 64),
           ("4a", 480, 14, 192, 96, 208, 16, 48, 64),
           ("4b", 512, 14, 160, 112, 224, 24, 64, 64),
           ("4c", 512, 14, 128, 128, 256, 24, 64, 64),
           ("4d", 512, 14, 112, 144, 288, 32, 64, 64),
           ("4e", 528, 14, 256, 160, 320, 32, 128, 128),
           ("5a", 832, 7, 256, 160, 320, 32, 128, 128),
           ("5b", 832, 7, 384, 192, 384, 48, 128, 128)]


def test_googlenet_layers_are_table_1():
    cfg = cells.load_config("googlenet")
    want = []
    for m, ic, s, c1, r3, c3, r5, c5, pp in TABLE_1:
        want += [(f"G{m}-1x1", s, 1, ic, c1), (f"G{m}-3x3r", s, 1, ic, r3),
                 (f"G{m}-3x3", s + 2, 3, r3, c3), (f"G{m}-5x5r", s, 1, ic, r5),
                 (f"G{m}-5x5", s + 4, 5, r5, c5), (f"G{m}-pool", s, 1, ic, pp)]
    got = [(l["name"], l["size"], l["kernel"], l["ic"], l["oc"])
           for l in cfg["layers"]]
    assert got == want
    assert all(l["stride"] == 1 for l in cfg["layers"])
    assert cfg["input"] == [192, 28, 28]
    # each module's concat is the next module's input (Table 1's output)
    outs = [c1 + c3 + c5 + pp for _, _, _, c1, _, c3, _, c5, pp in TABLE_1]
    assert outs[:-1] == [row[1] for row in TABLE_1[1:]]
    assert outs[-1] == 1024


def test_googlenet_maps_as_stated_and_is_the_programs_network():
    """The search gives the file's groups and pruning (build_mapping
    refuses otherwise), and the file's layers and glue are the program's
    own `networks.googlenet` entry."""
    from repro.core import networks
    cfg = cells.load_config("googlenet")
    net = harness.build_mapping(cfg)
    assert [m.layer for m in net.layers] == networks.googlenet()
    assert net.glue == networks.googlenet_glue()
    assert net.total_cycles == 10046
    assert sum(len(m.tiles) for m in net.layers) == 73


def test_googlenet_useful_flops():
    cfg = cells.load_config("googlenet")
    mod = cells.reference_module(cfg)
    assert mod.useful_flops_per_image(cfg) == 919450112
    dense = copy.deepcopy(cfg)
    for lay in dense["layers"]:
        lay["groups"], lay["pruned"] = 1, []
    assert mod.useful_flops_per_image(dense) == 2207944704


def _inception_toy() -> dict:
    with open(DATA / "inception_toy.json") as f:
        return json.load(f)


def test_inception_toy_is_served_correct():
    """A configuration that gives every layer its branch glue goes the
    normal path (build_mapping -> PlanLadder -> execute_plan) and agrees
    with bench/references/googlenet.py."""
    from repro.exec import compile_plan
    cfg = _inception_toy()
    plan = compile_plan(harness.build_mapping(cfg),
                        executor_policy="mapped", batch=2)
    assert [lp.glue.kind for lp in plan.layers] == \
        ["branch", "chain", "branch", "chain", "branch", "join"] * 2
    res = _run(cfg)
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["worst_rel_err"]["value"] < 1e-4


def test_inception_toy_reference_pools_and_concats():
    """The reference's module against the same module written out in
    NumPy (float64): branches in the order 1x1, 3x3, 5x5, pool proj,
    ceil-mode 3x3/2 pool between the modules."""
    cfg = _inception_toy()
    mod = cells.reference_module(cfg)
    kernels, pool = mod.make_data(cfg, 2, seed=2**35 + 3)
    k = [np.asarray(w, np.float64) for w in kernels]
    x = np.asarray(pool, np.float64)

    def conv(x, w):                          # "same", groups 1
        kh = w.shape[0]
        p = kh // 2
        xp = np.pad(x, ((0, 0), (0, 0), (p, p), (p, p)))
        n = x.shape[2]
        return sum(np.einsum("bchw,co->bohw", xp[:, :, i:i + n, j:j + n],
                             w[i, j]) for i in range(kh) for j in range(kh))

    def pool3(x, stride):
        n = x.shape[2]
        out = -(-(n - 3) // stride) + 1 if stride == 2 else n
        lo = 1 if stride == 1 else 0
        xp = np.pad(x, ((0, 0), (0, 0), (lo, 3), (lo, 3)),
                    constant_values=-np.inf)
        return np.stack([np.stack(
            [xp[:, :, i * stride:i * stride + 3,
                j * stride:j * stride + 3].max((2, 3)) for j in range(out)],
            -1) for i in range(out)], -2)

    relu = lambda v: np.maximum(v, 0.0)       # noqa: E731
    assert all(lay["groups"] == 1 for lay in cfg["layers"])
    for m in range(2):
        if m:
            x = pool3(x, 2)
        w = k[6 * m:6 * m + 6]
        x = np.concatenate([relu(conv(x, w[0])),
                            relu(conv(relu(conv(x, w[1])), w[2])),
                            relu(conv(relu(conv(x, w[3])), w[4])),
                            relu(conv(pool3(x, 1), w[5]))], axis=1)
    got = mod.forward_blocks(cfg, kernels, np.asarray(pool), block=2)
    assert got.shape == (2, 28, 4, 4)
    assert reference.rel_err(got, x) < 1e-5


def _glue_reader_run():
    cfg = _inception_toy()
    cell = dataclasses.replace(_cell(cfg), traffic=dict(SMALL_MIX, max_batch=4))
    return (cells.metric_reader("glue_bytes_per_image"),
            run.Run(cell, None, {}, None, None, None))


def _raiser(error):
    def refuse(config):
        raise error
    return refuse


@pytest.mark.parametrize("error", [
    TypeError("GlueSpec.__init__() got an unexpected keyword argument "
              "'pool'"),
    ValueError("unknown glue kind 'branch'"),
], ids=["field", "kind"])
def test_glue_bytes_reader(monkeypatch, error):
    """The reader gives the program's counter in MB, and None where the
    program's ``GlueSpec`` refuses the configuration's glue (a field or
    a kind that a program without branch glue lacks)."""
    from repro.exec import compile_plan
    read, run_ = _glue_reader_run()
    plan = compile_plan(harness.build_mapping(run_.cell.config),
                        executor_policy="mapped", batch=4)
    assert read(run_) == plan.glue_bytes_per_image / 1e6 > 0
    monkeypatch.setattr(harness, "layer_glue", _raiser(error))
    assert read(run_) is None


@pytest.mark.parametrize("where,error", [
    ("layer_glue", ValueError("inception_toy: ['Ga-1x1'] carry no "
                              "\"glue\"; give every layer its glue, or "
                              "none")),
    ("layer_glue", TypeError("unsupported operand")),
    ("build_mapping", ValueError("Ga-1x1: the mapping groups by 2")),
], ids=["partial_glue", "other_type_error", "mapping_differs"])
def test_glue_bytes_reader_raises_other_errors(monkeypatch, where, error):
    """A configuration that is wrong (glue on some layers only, a
    mapping that differs from the file) raises through the reader, so it
    is not taken for a program that lacks the counter."""
    read, run_ = _glue_reader_run()
    monkeypatch.setattr(harness, where, _raiser(error))
    with pytest.raises(type(error)):
        read(run_)
