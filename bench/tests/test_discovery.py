"""A cell added as files alone is found by name."""
import json

import cells
import traffic


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


def test_the_repositorys_cells_have_every_file():
    spec = cells.load_spec()
    for w in spec["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell.rate_per_s > 0
        assert cell.config["check"]["worst_rel_err_limit"] > 0
        for m in cell.per_layer:
            assert callable(cells.metric_reader(m["name"]))
