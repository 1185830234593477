"""What the program runs on, and what it keeps between runs.

Every entry point (`serve_cnn.main`, `launch/train.py`, the replica
worker, `chip_smoke.py`) calls :func:`enable_compile_cache` before its
first compile and prints :func:`describe_devices`, so each result names
the platform, device kind and device count it came from.

The compile observer listens to JAX's compile events from this module's
import on and keeps, per jitted function, the seconds spent tracing,
lowering and compiling and the persistent-cache hits and misses
(:func:`compile_stats`).

The helpers that bind replica workers to chips (:func:`host_tpu_chips`,
:func:`chip_env`, :func:`expected_platform`) read only the environment
and the host's PCI listing: the router process that calls them never
starts a JAX backend, so every chip stays free for its worker.
"""
from __future__ import annotations

import glob
import os
import re
import threading
from pathlib import Path
from typing import Dict, Mapping, Optional, Sequence, Tuple

#: The checkout's fixed compile-cache directory (git-ignored).  Fixed,
#: because the path is part of the cache key: a moving directory never
#: hits.
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_COMPILE_CACHE = REPO_ROOT / ".jax_cache"

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir(environ: Mapping[str, str] = os.environ
                      ) -> Tuple[str, bool]:
    """``(directory, from_env)``: ``$JAX_COMPILATION_CACHE_DIR`` when it
    is set (JAX reads it itself), else :data:`DEFAULT_COMPILE_CACHE`."""
    env = environ.get(_CACHE_ENV)
    if env:
        return env, True
    return str(DEFAULT_COMPILE_CACHE), False


def enable_compile_cache(platform: Optional[str] = None) -> Optional[str]:
    """Turn on JAX's persistent compilation cache and return its
    directory (None when it stays off).  With
    ``$JAX_COMPILATION_CACHE_DIR`` set, JAX already uses it and nothing
    is set here.  Otherwise an accelerator ``platform`` (default: the
    backend JAX starts) caches in the checkout's fixed
    :data:`DEFAULT_COMPILE_CACHE`; the CPU, where tests and
    interpret-mode rehearsals run, keeps no cache.  On an accelerator
    every compiled program is kept: a serving ladder is many sub-second
    compiles, each below JAX's default one-second write threshold."""
    import jax
    path, from_env = compile_cache_dir()
    if (platform or jax.default_backend()) == "cpu":
        return path if from_env else None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# Compile observer
# ---------------------------------------------------------------------------

#: JAX's compile phases, each reported with the jitted function's name:
#: tracing to a jaxpr, lowering it to an MLIR module, and the backend
#: compile (which includes a persistent-cache lookup and load).
_PHASE_SECONDS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration":
                      "lower_s",
                  "/jax/core/compile/backend_compile_duration": "compile_s"}
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_STAT_KEYS = ("hits", "misses", "trace_s", "lower_s", "compile_s")
_FUN = re.compile(r"\w+\((.*)\)")


def _fun(name: str) -> str:
    """``_execute_jit`` from ``_execute_jit`` or ``jit(_execute_jit)``:
    JAX names the tracing phase by the function, the others by its
    module."""
    m = _FUN.fullmatch(name)
    return m.group(1) if m else name


class _CompileObserver:
    """Seconds of each compile phase and cache lookups, per function.

    Phases nest: tracing a jitted function traces every jitted function
    it calls, and a constant it computes eagerly compiles mid-trace.
    Each phase's start is reported too, so only the outermost phase of
    a thread is counted, under its own function, and inner lookups count
    for that function: the three kinds of seconds never overlap."""

    def __init__(self):
        self.by_fun: Dict[str, Dict[str, float]] = {}
        self._local = threading.local()

    def _open(self) -> list:
        open_ = getattr(self._local, "open", None)
        if open_ is None:
            open_ = self._local.open = []
        return open_

    def _row(self, fun: str) -> Dict[str, float]:
        row = self.by_fun.get(fun)
        if row is None:
            row = self.by_fun[fun] = dict.fromkeys(_STAT_KEYS, 0)
        return row

    def started(self, event: str, _value, *, fun_name: str = "",
                **_kw) -> None:
        if event in _PHASE_SECONDS:
            self._open().append(_fun(fun_name))

    def finished(self, event: str, secs: float, *, fun_name: str = "",
                 **_kw) -> None:
        key = _PHASE_SECONDS.get(event)
        if key is None:
            return
        open_ = self._open()
        if open_:
            open_.pop()
        if not open_:
            self._row(_fun(fun_name))[key] += secs

    def counted(self, event: str, **_kw) -> None:
        if event not in (_CACHE_REQUEST, _CACHE_HIT):
            return
        open_ = self._open()
        row = self._row(open_[0] if open_ else "")
        if event == _CACHE_HIT:
            row["hits"] += 1
            row["misses"] -= 1      # every hit was first a request
        else:
            row["misses"] += 1

    def stats(self, funs: Sequence[str]) -> Dict[str, float]:
        rows = [self.by_fun[f] for f in funs if f in self.by_fun] \
            if funs else list(self.by_fun.values())
        return {k: sum(r[k] for r in rows) for k in _STAT_KEYS}


def _observe() -> _CompileObserver:
    from jax import monitoring
    obs = _CompileObserver()
    monitoring.register_scalar_listener(obs.started)
    monitoring.register_event_duration_secs_listener(obs.finished)
    monitoring.register_event_listener(obs.counted)
    return obs


_observer = _observe()


def compile_stats(*funs: str) -> Dict[str, float]:
    """What this process has compiled since this module was imported:
    persistent-cache ``hits`` and ``misses`` (lookups that compiled), and
    the seconds of ``trace_s``, ``lower_s`` and ``compile_s`` (the
    backend compile, or the load on a cache hit).  With ``funs``, only
    the jitted functions of those names and what compiled while they
    were traced or lowered."""
    return _observer.stats(funs)


def device_summary() -> Dict[str, object]:
    """``{"platform", "kind", "count"}`` as JAX reports them — starts the
    backend, so call it from the process that owns the devices."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def describe_devices() -> str:
    d = device_summary()
    return (f"devices: platform={d['platform']} kind={d['kind']} "
            f"count={d['count']}")


# ---------------------------------------------------------------------------
# One process per chip (replica workers)
# ---------------------------------------------------------------------------

_GOOGLE_PCI_VENDOR = "0x1ae0"
#: PCI device ids of TPU chips (v4, v5p, v5e, v6e).
_TPU_PCI_DEVICES = frozenset({"0x005e", "0x0062", "0x0063", "0x006f"})


def host_tpu_chips(pci_root: str = "/sys/bus/pci/devices") -> int:
    """TPU chips attached to this host, counted from its PCI listing
    without loading the TPU runtime."""
    n = 0
    for vendor in glob.glob(os.path.join(pci_root, "*", "vendor")):
        dev = os.path.join(os.path.dirname(vendor), "device")
        try:
            if (Path(vendor).read_text().strip() == _GOOGLE_PCI_VENDOR
                    and Path(dev).read_text().strip() in _TPU_PCI_DEVICES):
                n += 1
        except OSError:
            continue
    return n


def expected_platform(environ: Mapping[str, str] = os.environ,
                      chips: Optional[int] = None) -> str:
    """The platform a fresh process on this host comes up on: the first
    entry of ``$JAX_PLATFORMS`` when set, else ``"tpu"`` when the host
    has TPU chips, else ``"cpu"``."""
    forced = environ.get("JAX_PLATFORMS", "").split(",")[0].strip()
    if forced:
        return forced
    return "tpu" if (host_tpu_chips() if chips is None else chips) else "cpu"


def chip_env(chip: int) -> Dict[str, str]:
    """Environment that binds a fresh process to TPU chip ``chip`` alone:
    it sees that one chip as a one-chip slice of its own, and may not
    fall back to the CPU.  Applied before the process imports JAX."""
    return {
        "JAX_PLATFORMS": "tpu",
        "CLOUD_TPU_TASK_ID": "0",
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_PORT": str(8476 + chip),
    }


def held_device_nodes(fd_dir: str = "/proc/self/fd") -> Tuple[str, ...]:
    """Accelerator device nodes this process holds open (``accel0``,
    ``vfio/2``, ...), read from its file descriptors: which physical
    chip a bound process really drives, whatever its runtime numbers
    it.  Empty off Linux or before the backend starts."""
    held = set()
    for fd in glob.glob(os.path.join(fd_dir, "*")):
        try:
            target = os.readlink(fd)
        except OSError:
            continue
        if target.startswith("/dev/accel") or (
                target.startswith("/dev/vfio/")
                and target != "/dev/vfio/vfio"):
            held.add(target[len("/dev/"):])
    return tuple(sorted(held))


def check_replica_count(n_replicas: int, platform: str,
                        chips: Optional[int] = None) -> None:
    """Refuse more one-chip replicas than the host has chips."""
    if platform != "tpu":
        return
    chips = host_tpu_chips() if chips is None else chips
    if n_replicas > chips:
        raise ValueError(
            f"--replicas {n_replicas} needs one TPU chip per replica, but "
            f"this host has {chips}")
