"""Device time by layer scope and phase, and idle time by host span: on a
made-up table, and on traces recorded on a TPU v5e before and after the
program named its work."""
from pathlib import Path
from types import SimpleNamespace

import pytest

import cells
import scopes
import trace_reduce as tr

DATA = Path(__file__).parent / "data"
UNNAMED = DATA / "cnn8_tpu_v5e.xplane.pb"           # no scopes, no spans
SCOPED = DATA / "cnn8_tpu_v5e_scoped.xplane.pb"
CNN8 = [f"L{i:02d}.CNN8-{i + 2}" for i in range(6)]


@pytest.mark.parametrize("path,want", [
    ("jit(_execute_jit)/L03.CNN8-5/macro/dot_general:", ("L03.CNN8-5",
                                                         "macro")),
    ("jit(f)/checkpoint/L12.DN40-t1/glue/concatenate:", ("L12.DN40-t1",
                                                          "glue")),
    # the first phase after the layer, however deep
    ("jit(f)/L00.a/weights/jit(build)/place/pad:", ("L00.a", "weights")),
    ("jit(f)/L00.a/patches/x;jit(f)/L01.b/place/y", ("L00.a", "patches")),
    ("jit(f)/L01.b/reshape:", ("L01.b", "unscoped")),
    ("jit(f)/macro/copy:", ("unscoped", "unscoped")),
    ("", ("unscoped", "unscoped")),
])
def test_op_scope(path, want):
    assert scopes.op_scope(path) == want


def test_self_times_count_nested_time_once():
    evs = [(0, 100, "while"), (10, 30, "a"), (40, 50, "b"), (200, 210, "c")]
    own = {n: t for _, _, n, t in scopes.self_times(evs)}
    assert own == {"while": 70, "a": 20, "b": 10, "c": 10}


def test_reduce_by_layer_phase_idle_and_spans():
    ms = 10**6
    host = [(0, 100 * ms, "bench.window"),
            (10 * ms, 20 * ms, "bench.prepare"),
            (12 * ms, 14 * ms, "repro.plan_for"),
            (20 * ms, 24 * ms, "bench.dispatch"),
            (21 * ms, 23 * ms, "repro.execute_plan"),
            (60 * ms, 61 * ms, "repro.execute_plan"),
            (150 * ms, 151 * ms, "repro.execute_plan")]    # after the close
    dev = {"/device:TPU:0": [
        (25 * ms, 40 * ms, "jit(f)/L00.a/weights/fusion:", ""),
        (40 * ms, 45 * ms, "jit(f)/L00.a/macro/dot_general:", ""),
        (45 * ms, 50 * ms, "jit(f)/L01.b/place/scatter:", ""),
        (50 * ms, 52 * ms, "jit(f)/L01.b/reshape:", ""),
        (52 * ms, 55 * ms, "", "xla:copy"),
        (55 * ms, 59 * ms, "jit(f)/L01.b/place/scatter:", "xla:broadcast"),
        (95 * ms, 110 * ms, "jit(f)/L01.b/macro/dot_general:", "")]}
    sc = scopes.reduce(host, dev)
    assert sc.window_s == pytest.approx(0.1)
    assert sc.device_s == pytest.approx(0.039)
    assert sc.device_by_layer == pytest.approx(
        {"L00.a": 0.020, "L01.b": 0.016, "unscoped": 0.003})
    assert sc.device_by_phase == pytest.approx(
        {"weights": 0.015, "macro": 0.010, "place": 0.009,
         "unscoped": 0.005})
    assert sc.unscoped == {"xla:copy": pytest.approx(0.003)}
    assert sc.attributed == {"xla:broadcast": pytest.approx(0.004)}
    assert sc.array_pass_share == pytest.approx(10 / 39)
    assert sc.scoped_share == pytest.approx(36 / 39)
    assert sc.named_share == pytest.approx(32 / 39)
    # gaps [0, 25) (middle 12.5 ms, in repro.plan_for inside
    # bench.prepare) and [59, 95) (middle 77 ms, in no span)
    assert sc.idle_by_span == [("sleep", 1, pytest.approx(0.036)),
                               ("repro.plan_for", 1, pytest.approx(0.025))]
    assert sc.spans == {"repro.plan_for": (1, pytest.approx(0.002)),
                        "repro.execute_plan": (2, pytest.approx(0.003))}
    assert sc.dispatch_ms_per_batch == pytest.approx(1.5)
    assert sc.stale_names() is None


@pytest.mark.parametrize("text,want", [
    ("%copy.178 = bf16[6,8]{1,0:T(8,128)(2,1)S(1)} copy(f32[6,8]"
     "{0,1:T(8,128)S(1)} %fusion.10), metadata={}", ("copy.178",
                                                     ["fusion.10"])),
    # a tuple shape, layouts with parentheses, a called computation
    ("%copy-start.6 = (s32[48,3]{1,0:T(8,128)S(1)}, u32[]{:S(2)}) "
     "copy-start(s32[48,3]{1,0:T(8,128)} %constant.690)",
     ("copy-start.6", ["constant.690"])),
    ("%fusion.7 = bf16[25,64]{1,0} fusion(bf16[64,5]{1,0} %copy.235, "
     "s32[25]{0:T(128)S(1)} %reshape.577), kind=kCustom, "
     "calls=%fused_computation.7", ("fusion.7", ["copy.235",
                                                  "reshape.577"])),
    ("ROOT %tuple = (f32[2]) tuple(f32[2] %a)", ("tuple", ["a"])),
    ("async-copy", ("async-copy", [])),
])
def test_hlo_operands(text, want):
    assert scopes.hlo_operands(text) == want


def test_attribute_gives_compiler_ops_their_neighbours_scope():
    """A compiler-made op takes the scope of what it reads, through other
    compiler-made ops, before that of what reads it; names match within
    one program only."""
    m = scopes.Meta
    ops = {
        1: m("%slice.1 = f32[4] slice(f32[8] %p)", "jit(f)/L00.a/patches/s",
             7),
        2: m("%copy.1 = f32[4] copy(f32[4] %slice.1)", "", 7),
        3: m("%copy.2 = f32[4] copy(f32[4] %copy.1)", "", 7),
        4: m("%broadcast.1 = f32[4] broadcast(f32[] %constant.1)", "", 7),
        5: m("%fusion.1 = f32[4] fusion(f32[4] %broadcast.1, f32[4] "
             "%copy.2), kind=kCustom", "jit(f)/L01.b/place/scatter", 7),
        6: m("%copy.3 = f32[4] copy(f32[4] %slice.1)", "", 9),
        7: m("%add.1 = s32[] add(s32[] %p.0, s32[] %constant.2)", "", 7),
    }
    got = scopes.attribute(ops)
    assert got[2] == got[3] == "jit(f)/L00.a/patches/s"
    assert got[4] == "jit(f)/L01.b/place/scatter"
    assert got[6] == got[7] == ""
    assert got[1] == ops[1].path and got[5] == ops[5].path


def test_stale_names_warn_when_dispatched_batches_ran_unnamed():
    host = [(0, 100, "bench.window"), (10, 20, "repro.execute_plan")]
    dev = {"/device:TPU:0": [(20, 60, "", "xla:copy"),
                             (60, 70, "jit(f)/L00.a/macro/dot:", "")]}
    assert "layer scope" in scopes.reduce(host, dev).stale_names()
    quiet = [(0, 100, "bench.window"), (10, 20, "bench.dispatch")]
    assert scopes.reduce(quiet, dev).stale_names() is None


def test_wire_reader_reads_what_profile_data_reads():
    """The protobuf reader finds the same host spans and device op times
    as ``jax.profiler.ProfileData`` on the same file."""
    host, dev = tr.read_xplane(str(UNNAMED))
    host2, dev2, mods = scopes.read_trace(str(UNNAMED))
    assert sorted(host2) == sorted(host)
    ops = sorted((s, e) for s, e, _ in dev["/device:TPU:0"])
    ops2 = sorted((s, e) for s, e, _, _ in dev2["/device:TPU:0"])
    assert len(ops2) == len(ops) == 1400
    assert all(abs(a - b) <= 1 for x, y in zip(ops, ops2)
               for a, b in zip(x, y))
    assert len(mods["/device:TPU:0"]) == 5


def test_a_program_without_names_reads_all_unscoped():
    sc = scopes.reduce_file(str(UNNAMED))
    assert sc.scoped_share == 0.0 and sc.array_pass_share == 0.0
    assert sc.attributed == {}
    assert sc.dispatch_ms_per_batch is None
    assert sc.stale_names() is None         # nor did it name its calls
    assert sc.device_s == pytest.approx(tr.reduce_file(str(UNNAMED)).busy_s,
                                        rel=1e-3)


def _scoped_ops():
    host, dev, mods = scopes.read_trace(str(SCOPED))
    return host, dev["/device:TPU:0"], mods["/device:TPU:0"]


def test_recorded_device_time_resolves_to_layer_scopes():
    """cnn8 batches of 64 served through the window on one TPU v5e by
    the program that names its work: nearly all device op time lies in
    a layer scope, and what does not is named by what made it."""
    host, ops, _ = _scoped_ops()
    own = scopes.self_times([(s, e, n) for s, e, n, made in ops if not made])
    total = sum(t for *_, t in scopes.self_times(
        [(s, e, n) for s, e, n, _ in ops]))
    scoped = sum(t for _, _, n, t in own
                 if scopes.op_scope(n)[0] != scopes.UNSCOPED)
    assert scoped >= 0.9 * total
    sc = scopes.reduce(host, {"/device:TPU:0": ops})
    made = {m for *_, m in ops if m}
    assert set(sc.unscoped) <= {"named"} | made
    assert set(sc.attributed) <= made
    # data flow finds a scope for nearly all the compiler's own ops
    assert sc.scoped_share >= 0.99 > sc.named_share >= 0.9
    assert sc.stale_names() is None


def test_recorded_module_runs_hold_every_layer():
    _, ops, mods = _scoped_ops()
    assert len(mods) >= 3
    for s, e, _ in mods:
        layers = {scopes.op_scope(n)[0] for a, b, n, _ in ops
                  if s <= a and b <= e} - {scopes.UNSCOPED}
        assert sorted(layers) == CNN8


def test_recorded_module_runs_start_after_their_dispatch_span():
    """The program's spans and the device share one clock: the k-th
    module run starts after the k-th ``repro.execute_plan`` span began
    and before the next one began."""
    host, _, mods = _scoped_ops()
    starts = sorted(s for s, _, n in host if n == scopes.DISPATCH)
    runs = sorted(s for s, _, _ in mods)
    assert len(starts) >= len(runs)
    for k, m in enumerate(runs):
        assert starts[k] < m
        if k + 1 < len(starts):
            assert m < starts[k + 1]


def test_dispatch_ms_per_batch_reads_the_span_around_execute_plan():
    """The metric's ``bench.dispatch`` span holds the program's
    ``repro.execute_plan`` span of each batch, and little else."""
    read = cells.metric_reader("dispatch_ms_per_batch.backlog")
    ms = read(SimpleNamespace(trace=tr.reduce_file(str(SCOPED))))
    program = scopes.reduce_file(str(SCOPED)).dispatch_ms_per_batch
    assert program <= ms <= program + 0.1
    assert read(SimpleNamespace(trace=None)) is None
    assert read(SimpleNamespace(trace=tr.reduce_file(str(UNNAMED)))) > 0
