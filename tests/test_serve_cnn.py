"""CNN serving driver (launch/serve_cnn.py): maps with the persistent
cache, serves batches through executor="mapped", reports images/s."""
import jax
import pytest

from repro.core import ArrayConfig, MacroGrid, memo
from repro.launch import serve_cnn


def test_serve_cnn_reports_images_per_s(capsys, tmp_path):
    """End-to-end acceptance: the driver maps CNN8 (populating the disk
    cache), runs batched mapped-executor steps, and reports images/s."""
    memo.clear()
    try:
        serve_cnn.main(["--net", "cnn8", "--batch", "2", "--steps", "2",
                        "--warmup", "1", "--grid", "2x2",
                        "--cache-dir", str(tmp_path)])
    finally:
        memo.set_disk_cache(None)
        memo.clear()
    out = capsys.readouterr().out
    assert "images/s" in out and "executor=mapped" in out
    assert "serve/cnn8/b2," in out            # harness CSV row
    assert list(tmp_path.glob("*.mapping.pkl"))   # cache populated


def test_map_for_serving_grid_and_budget_paths():
    m_grid, _ = serve_cnn.map_for_serving(
        "cnn8", ArrayConfig(512, 512), "Tetris-SDK", grid=MacroGrid(2, 1))
    assert m_grid.grid == MacroGrid(2, 1)
    m_sweep, secs = serve_cnn.map_for_serving(
        "cnn8", ArrayConfig(512, 512), "TetrisG-SDK", p_max=2)
    assert m_sweep.grid.p <= 2 and secs > 0


def test_serving_mesh_for_single_device():
    """On one device the driver falls back to the vmap path (mesh None)
    rather than a degenerate 1x1 shard_map."""
    m, _ = serve_cnn.map_for_serving("cnn8", ArrayConfig(512, 512),
                                     "Tetris-SDK", grid=MacroGrid(2, 2))
    if len(jax.devices()) == 1:
        assert serve_cnn.serving_mesh_for(m, batch=4) is None


def test_serve_returns_effective_and_padded_rates():
    """Without a data mesh the request batch needs no padding: plan
    batch == request batch and both rates agree."""
    m, _ = serve_cnn.map_for_serving("cnn8", ArrayConfig(512, 512),
                                     "Tetris-SDK", grid=MacroGrid(1, 1))
    s = serve_cnn.serve(m, batch=2, steps=1, warmup=1, mesh=None)
    assert s.plan_batch == s.request_batch == 2
    assert s.images_per_s == s.padded_images_per_s > 0
    assert s.warmup_steps == 1
    assert not s.donated                     # CPU: no donation


def test_serve_honors_warmup_zero(monkeypatch):
    """Regression: serve(warmup=0) used to run max(1, warmup) warmup
    steps — 0 must mean 0 (timing then includes compile) and the actual
    count surfaces in ServeStats.warmup_steps."""
    import repro.exec as exec_mod
    calls = []
    real = exec_mod.execute_plan

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(exec_mod, "execute_plan", counting)
    m, _ = serve_cnn.map_for_serving("cnn8", ArrayConfig(512, 512),
                                     "Tetris-SDK", grid=MacroGrid(1, 1))
    s = serve_cnn.serve(m, batch=1, steps=2, warmup=0, mesh=None)
    assert s.warmup_steps == 0
    assert len(calls) == 2               # exactly the timed steps
    calls.clear()
    s = serve_cnn.serve(m, batch=1, steps=2, warmup=3, mesh=None)
    assert s.warmup_steps == 3 and len(calls) == 5
    with pytest.raises(ValueError, match="warmup"):
        serve_cnn.serve(m, batch=1, steps=1, warmup=-1)


def _parse_kv(row: str) -> dict:
    return dict(kv.split("=") for kv in row.strip().split(",")[-1].split(";")
                if "=" in kv)


def test_main_search_stats_snapshot_regression(capsys, tmp_path):
    """Regression (memo.stats aliasing): the final CSV row must report
    the SEARCH-phase counters, not the live dict after serve() — plan
    compilation during serving hits the disk cache and used to leak
    into the reported search stats."""
    args = ["--net", "cnn8", "--batch", "2", "--steps", "1",
            "--warmup", "1", "--grid", "2x2", "--cache-dir",
            str(tmp_path)]
    memo.clear()
    try:
        serve_cnn.main(args)              # cold: populate mapping + plan
        memo.clear()                      # drop in-memory, keep disk
        capsys.readouterr()
        serve_cnn.main(args)              # warm: search AND plan disk-hit
        out = capsys.readouterr().out
        live_hits = memo.stats["disk_hits"]
    finally:
        memo.set_disk_cache(None)
        memo.clear()
    search_line = next(ln for ln in out.splitlines() if "search=" in ln)
    search_hits = int(search_line.split("disk_hits=")[1].split(" ")[0])
    csv = _parse_kv(next(ln for ln in out.splitlines()
                         if ln.startswith("serve/cnn8/")))
    assert int(csv["disk_hits"]) == search_hits
    assert int(csv["table_builds"]) == 0      # warm search: no builds
    # the plan load DID hit the disk after the snapshot — the live dict
    # would have reported more (this is what the old code leaked)
    assert live_hits > search_hits


def test_main_dynamic_batching_cli(capsys, tmp_path):
    """Dynamic mode end-to-end: --max-delay-ms drives the coalescer +
    tier ladder; per-tier and aggregate CSV rows come out, and every
    tier's plan compiled exactly once."""
    memo.clear()
    try:
        serve_cnn.main(["--net", "cnn8", "--grid", "2x2",
                        "--max-delay-ms", "1", "--max-batch", "4",
                        "--requests", "8", "--warmup", "1",
                        "--cache-dir", str(tmp_path)])
    finally:
        memo.set_disk_cache(None)
        memo.clear()
    out = capsys.readouterr().out
    assert "queue-delay p50=" in out
    agg = _parse_kv(next(ln for ln in out.splitlines()
                         if ln.startswith("serve_dyn/cnn8/all,")))
    assert agg["tiers"] == "1/2/4"
    assert int(agg["plan_compiles"]) == 3      # once per tier
    assert float(agg["images_per_s"]) > 0
    assert float(agg["padded_images_per_s"]) >= float(agg["images_per_s"])
    assert any(ln.startswith("serve_dyn/cnn8/tier") for ln
               in out.splitlines())


def test_dynamic_effective_rate_beats_fixed_ragged():
    """ISSUE 5 acceptance: on the same backlogged ragged stream the
    dynamic coalescer's effective images/s must be >= the fixed-batch
    driver's (interleaved medians, benchmarks/serve_bench.py)."""
    from benchmarks import serve_bench
    rows = serve_bench.run(full=False)
    by_name = {r.name: _parse_kv(r.csv()) for r in rows}
    fixed = float(by_name["serve_dyn/cnn8/fixed-ragged"]["images_per_s"])
    dyn = float(by_name["serve_dyn/cnn8/dynamic"]["images_per_s"])
    assert dyn >= fixed, (dyn, fixed)


def test_pad_to_data_axis():
    from repro.launch.mesh import data_axis_size, pad_to_data_axis

    class _FakeMesh:
        def __init__(self, **shape):
            self.axis_names = tuple(shape)
            self.shape = dict(shape)

    assert pad_to_data_axis(3, None) == 3
    plain = _FakeMesh(row=2, col=2)
    assert data_axis_size(plain) == 1 and pad_to_data_axis(3, plain) == 3
    data = _FakeMesh(data=2, row=2, col=2)
    assert data_axis_size(data) == 2
    assert pad_to_data_axis(3, data) == 4
    assert pad_to_data_axis(4, data) == 4
    assert pad_to_data_axis(1, data) == 2


def test_serve_ragged_batch_pads_and_masks():
    """Tentpole/satellite contract on 8 forced host devices: a request
    batch of 3 does NOT divide the serving mesh's data axis (2) — the
    driver pads to the plan batch (4), serves through the mesh, masks
    the padded row, and the 3 real outputs agree with the single-device
    vmap plan to the reassociation rule (DESIGN.md §7).  Pad-and-mask
    isolation is total: garbage in the padded row leaves the request
    rows bit-identical, and the
    masked loss's input gradient matches the vmap plan on the request
    rows with an exactly-zero gradient on the padded row."""
    import os
    import subprocess
    import sys
    code = """
import numpy as np, jax, jax.numpy as jnp
from repro.core import ArrayConfig, MacroGrid, map_net, networks
from repro.cnn.mapped_net import zero_pruned_kernels
from repro.exec import (REASSOC_RTOL, compile_plan, execute_plan,
                        reassoc_error)
from repro.launch import serve_cnn
from repro.launch.mesh import pad_to_data_axis, serving_mesh_for
assert len(jax.devices()) == 8
net = map_net("cnn8", networks.cnn8()[:3], ArrayConfig(64, 64),
              "Tetris-SDK", MacroGrid(2, 2))
mesh = serving_mesh_for(net, 3)
assert dict(mesh.shape) == {"data": 2, "row": 2, "col": 2}, dict(mesh.shape)
assert pad_to_data_axis(3, mesh) == 4
s = serve_cnn.serve(net, batch=3, steps=1, warmup=1, mesh=mesh)
assert s.request_batch == 3 and s.plan_batch == 4
assert abs(s.padded_images_per_s / s.images_per_s - 4 / 3) < 1e-6
# masked outputs == vmap plan on the same 3 images (reassociation rule)
rng = np.random.RandomState(0)
ks = zero_pruned_kernels(net, [
    jnp.asarray(rng.randn(m.layer.k_h, m.layer.k_w,
                          m.layer.ic // m.group, m.layer.oc) * 0.2,
                jnp.float32) for m in net.layers])
first = net.layers[0].layer
x3 = jnp.asarray(rng.randn(3, first.ic, first.i_h, first.i_w), jnp.float32)
x4 = jnp.pad(x3, ((0, 1), (0, 0), (0, 0), (0, 0)))
plan = compile_plan(net, executor_policy="mapped", mesh=mesh, batch=4)
y = execute_plan(plan, ks, x4, mesh=mesh)[:3]
vmap_plan = compile_plan(net, executor_policy="mapped")
y_ref = execute_plan(vmap_plan, ks, x3)
assert reassoc_error(y, y_ref) <= REASSOC_RTOL, \
    "masked sharded outputs != vmap"
# isolation: garbage in the padded row must not touch request rows
x4_dirty = x4.at[3].set(7.5)
y_dirty = execute_plan(plan, ks, x4_dirty, mesh=mesh)[:3]
assert bool(jnp.all(y_dirty == y)), "padded row leaked into outputs"
# gradient isolation: masked loss -> request-row grads match the vmap
# plan, padded-row grad exactly zero
g4 = jax.grad(lambda xx: jnp.sum(
    execute_plan(plan, ks, xx, mesh=mesh)[:3] ** 2))(x4)
g3 = jax.grad(lambda xx: jnp.sum(
    execute_plan(vmap_plan, ks, xx) ** 2))(x3)
scale = float(jnp.max(jnp.abs(g3)))
assert float(jnp.max(jnp.abs(g4[:3] - g3))) <= 1e-6 * scale, \
    "request-row grads drift under pad-and-mask"
assert bool(jnp.all(g4[3] == 0)), "padded row has nonzero gradient"
print("RAGGED-OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + sys.path))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "RAGGED-OK" in out.stdout, out.stderr[-2000:]
