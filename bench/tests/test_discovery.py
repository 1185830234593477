"""A cell added as files alone is found by name."""
import ast
import json
from pathlib import Path

import pytest

import cells
import traffic

REFERENCE_FUNCTIONS = ("make_data", "forward_blocks",
                       "useful_flops_per_image")
TOY = Path(__file__).resolve().parent / "data" / "residual_toy.json"


def _write(path, obj):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


def test_a_cell_added_as_files_is_found_by_name(tmp_path):
    spec = {
        "configs": [{"name": "toy", "source": "https://example.org/toy",
                     "file": "bench/configs/toy.json", "reduced": [],
                     "why": "throwaway"}],
        "workloads": [{"name": "toy.burst", "config": "toy",
                       "traffic": "burst", "chips": 1, "why": "throwaway"}],
        "end_to_end": [
            {"name": "images_per_s", "unit": "images/s", "better": "higher",
             "bound": 0.05, "source": "host_clock"},
            {"name": "latency_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.05, "source": "host_clock",
             "workloads": ["other.cell"]}],
        "per_layer": [
            {"name": "toy_metric", "unit": "ms", "better": "lower",
             "source": "host_clock", "layer": "toy", "moves": "images_per_s",
             "workloads": ["toy.burst"]},
            {"name": "toy_metric.split", "unit": "ms", "better": "lower",
             "source": "host_clock", "layer": "toy", "moves": "images_per_s",
             "workloads": ["toy.burst"]},
            {"name": "not_here", "unit": "ms", "better": "lower",
             "source": "host_clock", "layer": "toy", "moves": "images_per_s",
             "workloads": ["other.cell"]}],
    }
    _write(tmp_path / "BENCHMARK.json", spec)
    _write(tmp_path / "bench/configs/toy.json", {"name": "toy", "layers": []})
    mix = {"arrivals": "poisson", "pool_images": 64, "max_batch": 8,
           "max_delay_ms": 1.0,
           "sizes": {"zipf_exponent": 1.2, "min": 1, "max": 4}}
    _write(tmp_path / "bench/traffic/burst.json", mix)
    _write(tmp_path / "bench/cells/toy.burst.json", {"rate_per_s": 123.0})
    reader = tmp_path / "bench/metrics/toy_metric.py"
    reader.parent.mkdir()
    reader.write_text("def read(run):\n    return run * 2\n")

    cell = cells.load_cell("toy.burst", root=tmp_path)
    assert cell.config == {"name": "toy", "layers": []}
    assert cell.traffic == mix
    assert cell.rate_per_s == 123.0
    assert [m["name"] for m in cell.end_to_end] == ["images_per_s"]
    assert [m["name"] for m in cell.per_layer] == ["toy_metric",
                                                   "toy_metric.split"]
    assert cells.metric_reader("toy_metric", root=tmp_path)(21) == 42
    # a split metric without a reader of its own takes its stem's
    assert cells.metric_reader("toy_metric.split", root=tmp_path)(4) == 8
    sched = traffic.schedule(cell.traffic, cell.rate_per_s, 2.0, seed=9)
    assert len(sched) == 246 and sched.sizes.max() <= 4


def _imported(path) -> set:
    """Top-level names of the modules a file imports."""
    names = set()
    for node in ast.walk(ast.parse(Path(path).read_text())):
        if isinstance(node, ast.Import):
            names.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            names.add(node.module.split(".")[0])
    return names


def _check_reference(config: dict, root=cells.ROOT) -> None:
    """The configuration's reference loads, has the three functions and
    imports nothing of the program (nor does a bench module it imports);
    its layers carry ``"glue"`` all or none."""
    mod = cells.reference_module(config, root=root)
    for fn in REFERENCE_FUNCTIONS:
        assert callable(getattr(mod, fn)), (config["name"], fn)
    files = [Path(mod.__file__)]
    for f in files:
        names = _imported(f)
        assert "repro" not in names, (config["name"], f)
        files += [root / "bench" / f"{n}.py" for n in sorted(names)
                  if (root / "bench" / f"{n}.py").exists()
                  and root / "bench" / f"{n}.py" not in files]
    glued = {"glue" in lay for lay in config["layers"]}
    assert len(glued) <= 1, config["name"]


def test_the_repositorys_cells_have_every_file():
    spec = cells.load_spec()
    for w in spec["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell.rate_per_s > 0
        assert cell.config["check"]["worst_rel_err_limit"] > 0
        for m in cell.per_layer:
            assert callable(cells.metric_reader(m["name"]))
    # every configuration, and the test toy that names its own reference
    for c in spec["configs"]:
        _check_reference(cells.load_config(c["name"]))
    _check_reference(json.loads(TOY.read_text()))


def test_a_configuration_names_its_own_reference(tmp_path):
    ref = tmp_path / "bench/references/toy.py"
    ref.parent.mkdir(parents=True)
    ref.write_text(
        "import numpy as np\n"
        "def make_data(config, pool_images, seed):\n"
        "    return (), np.full((pool_images, 1), seed)\n"
        "def forward_blocks(config, kernels, rows, *, block, operands=None):\n"
        "    return rows\n"
        "def useful_flops_per_image(config):\n"
        "    return 7.0\n")
    config = {"name": "toy", "reference": "bench/references/toy.py",
              "layers": [{"name": "L", "glue": {"kind": "last"}}]}
    _check_reference(config, root=tmp_path)
    mod = cells.reference_module(config, root=tmp_path)
    assert Path(mod.__file__) == ref.resolve()
    assert mod.useful_flops_per_image(config) == 7.0
    assert mod.make_data(config, 2, 5)[1].tolist() == [[5], [5]]
    # a configuration without the key takes bench/reference.py of its root
    default = tmp_path / "bench/reference.py"
    default.write_text(ref.read_text().replace("7.0", "8.0"))
    assert cells.reference_module({"name": "other"}, root=tmp_path) \
        .useful_flops_per_image({}) == 8.0
    # and none outside bench/
    outside = dict(config, reference="../elsewhere.py")
    with pytest.raises(ValueError, match="not a module under bench/"):
        cells.reference_module(outside, root=tmp_path)
