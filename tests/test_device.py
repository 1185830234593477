"""Device facts, the compile cache, and one process per chip — checked
with fake environments, fake PCI listings and fake devices, so no test
here starts a TPU runtime."""
from types import SimpleNamespace

import jax
import pytest

from repro.launch import device
from repro.launch.replica import (WorkerConfig, check_worker_platform,
                                  serve_replicas, worker_env)


def test_compile_cache_dir_honours_env_else_fixed_path():
    assert device.compile_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/cache/here"}) == ("/cache/here", True)
    a = device.compile_cache_dir({})
    b = device.compile_cache_dir({})
    assert a == b == (str(device.DEFAULT_COMPILE_CACHE), False)
    assert device.DEFAULT_COMPILE_CACHE.name == ".jax_cache"
    assert device.DEFAULT_COMPILE_CACHE.parent == device.REPO_ROOT


def test_enable_compile_cache_sets_no_dir_when_env_set(monkeypatch,
                                                       tmp_path):
    before = jax.config.jax_compilation_cache_dir
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert device.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert device.enable_compile_cache(platform="cpu") is None
        assert jax.config.jax_compilation_cache_dir == before
        assert device.enable_compile_cache(platform="tpu") == str(
            device.DEFAULT_COMPILE_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(
            device.DEFAULT_COMPILE_CACHE)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert set(device.compile_stats()) == {
            "hits", "misses", "trace_s", "lower_s", "compile_s"}
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_s)


def test_compile_stats_times_each_phase_of_a_fresh_jit_once():
    """A fresh jit adds trace, lower and compile seconds, under its own
    name too; calling it again adds nothing.  The jitted function it
    calls is traced inside it and not counted twice."""
    @jax.jit
    def _inner_probe(x):
        return x * 3

    @jax.jit
    def _outer_probe(x):
        return _inner_probe(x) + 1

    before = device.compile_stats()
    _outer_probe(jax.numpy.arange(5.0)).block_until_ready()
    mid = device.compile_stats()
    own = device.compile_stats("_outer_probe")
    for k in ("trace_s", "lower_s", "compile_s"):
        assert mid[k] > before[k]
        assert own[k] > 0
    assert own["hits"] + own["misses"] >= 1
    assert device.compile_stats("_inner_probe") == dict.fromkeys(own, 0)
    assert own["trace_s"] <= mid["trace_s"] - before["trace_s"]
    _outer_probe(jax.numpy.arange(5.0)).block_until_ready()
    assert device.compile_stats() == mid


def _fake_pci(root, devices):
    for i, (vendor, dev) in enumerate(devices):
        d = root / f"0000:00:0{i}.0"
        d.mkdir()
        (d / "vendor").write_text(vendor + "\n")
        (d / "device").write_text(dev + "\n")
    return str(root)


def test_host_tpu_chips_counts_tpu_pci_devices(tmp_path):
    root = _fake_pci(tmp_path, [("0x1ae0", "0x0063")] * 4
                     + [("0x8086", "0x0063"), ("0x1ae0", "0x9999")])
    assert device.host_tpu_chips(root) == 4
    assert device.host_tpu_chips(str(tmp_path / "missing")) == 0


def test_expected_platform():
    assert device.expected_platform({"JAX_PLATFORMS": "cpu"}, chips=4) \
        == "cpu"
    assert device.expected_platform({}, chips=4) == "tpu"
    assert device.expected_platform({}, chips=0) == "cpu"


def test_worker_env_binds_each_worker_to_its_own_chip():
    cfg = WorkerConfig(platform="tpu")
    envs = [worker_env(w, cfg, {}) for w in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    for e in envs:
        assert e["JAX_PLATFORMS"] == "tpu"      # never the CPU fallback
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
    # off a TPU host nothing is bound; forced host devices still apply
    host = worker_env(1, WorkerConfig(platform="cpu", xla_host_devices=4),
                      {"XLA_FLAGS": "--foo"})
    assert host == {"XLA_FLAGS": "--foo "
                    "--xla_force_host_platform_device_count=4"}
    assert worker_env(0, WorkerConfig(), {}) == {}


def test_worker_refuses_the_wrong_platform():
    cpu = [SimpleNamespace(platform="cpu", device_kind="cpu", id=0)]
    tpu = [SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                           id=2)]
    with pytest.raises(RuntimeError, match="came up on cpu"):
        check_worker_platform(0, WorkerConfig(platform="tpu"), cpu, {})
    bound = worker_env(2, WorkerConfig(platform="tpu"), {})
    assert check_worker_platform(2, WorkerConfig(platform="tpu"), tpu,
                                 bound) == "tpu:TPU v5 lite#2 chip2"
    assert check_worker_platform(2, WorkerConfig(platform="tpu"), tpu,
                                 bound, ("vfio/2",)) == \
        "tpu:TPU v5 lite#2@vfio/2 chip2"
    assert check_worker_platform(0, WorkerConfig(), cpu, {}) == "cpu:cpu#0"


def test_held_device_nodes_reads_open_accelerator_nodes(tmp_path):
    fds = tmp_path / "fd"
    fds.mkdir()
    for fd, target in enumerate(["/dev/vfio/vfio", "/dev/vfio/2",
                                 "/dev/accel1", "/dev/null", "socket:[7]"]):
        (fds / str(fd)).symlink_to(target)
    assert device.held_device_nodes(str(fds)) == ("accel1", "vfio/2")
    assert device.held_device_nodes(str(tmp_path / "missing")) == ()


def test_more_replicas_than_chips_is_refused():
    device.check_replica_count(4, "tpu", chips=4)
    device.check_replica_count(8, "cpu", chips=0)    # host processes
    with pytest.raises(ValueError, match="one TPU chip per replica"):
        device.check_replica_count(5, "tpu", chips=4)

    class NoSpawn:
        blocks = True

        def start_worker(self, wid, cfg):
            raise AssertionError("refused before any worker starts")

    # this host has no TPU chips: even one TPU replica is refused
    with pytest.raises(ValueError, match="one TPU chip per replica"):
        serve_replicas([(0.0, 1)], WorkerConfig(platform="tpu"), 1,
                       transport=NoSpawn())
