"""Shared plumbing of the chip benchmark: files, seeds, device, timing.

Everything that belongs to one configuration, traffic mix, driver or
per-layer metric lives in a file of its own under ``benchmarks/chip`` and is
found here by the name ``BENCHMARK.json`` gives it:

    configs/<config>.json        sizes, source, cut, reference module
    traffic/<traffic>.json       the mix: driver, policy, lengths, clients
    drivers/<driver>.py          one kind of driven work (``run(ctx)``)
    metrics/<metric>.py          one per-layer metric (``read(ctx)``)
    kernel_costs/<kind>.py       operations and bytes of one kernel kind
    references/<module>.py       plain reference of one model family
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Optional

import numpy as np

#: ``benchmarks/chip`` — the benchmark's own directory
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: root of the checkout (``BENCHMARK.json`` lives here)
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
#: fixed compile-cache path inside the checkout (the path is part of the
#: cache key, so it never moves)
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")


class BenchError(RuntimeError):
    """A run that cannot measure (no chip, missing file, bad cell)."""


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: Optional[str] = None):
    """Import a benchmark file by path (its name may hold dots)."""
    if not os.path.isfile(path):
        raise BenchError(f"missing benchmark file {path}")
    name = name or "bench_" + os.path.relpath(path, BENCH_DIR).replace(
        os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def bench_file(*parts: str) -> str:
    return os.path.join(BENCH_DIR, *parts)


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_cell(name: str, bench_path: Optional[str] = None) -> Cell:
    bench = load_json(bench_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(ROOT, confs[w["config"]]["file"]))
    traffic = load_json(bench_file("traffic", w["traffic"] + ".json"))

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


# ---------------------------------------------------------------------------
# Seeds
# ---------------------------------------------------------------------------

def seed_words(seed: int, tag: int = 0) -> np.ndarray:
    """Two uint32 words drawn from ``(seed, tag)``; any non-negative seed,
    including ones wider than 32 bits."""
    ss = np.random.SeedSequence([int(seed) & (2 ** 64 - 1), int(tag)])
    return ss.generate_state(2, np.uint32)


def seed_key(seed: int, tag: int = 0):
    """A JAX threefry key made from ``(seed, tag)``."""
    import jax
    return jax.random.wrap_key_data(seed_words(seed, tag),
                                    impl="threefry2x32")


def host_rng(seed: int, tag: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), int(tag)])


# ---------------------------------------------------------------------------
# Device, compile cache, compile counter
# ---------------------------------------------------------------------------

def use_compile_cache() -> str:
    """Persistent compilation cache at a fixed path inside the checkout;
    every program is cached, however short its compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return CACHE_DIR


def require_chips(n: int) -> None:
    """Refuse to run anywhere but on at least ``n`` TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < n:
        raise BenchError(f"needs {n} chips, JAX found {len(devs)}")


def device_info(n: int) -> Dict[str, Any]:
    import jax
    devs = jax.devices()[:n]
    peak = 0
    for d in devs:
        try:
            peak = max(peak, int(d.memory_stats()["peak_bytes_in_use"]))
        except Exception:          # backend without memory stats (CPU)
            peak = 0
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts lowerings and backend compiles while ``active``."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax._src import monitoring
        self.active = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event in self.EVENTS:
            self.count += 1


# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------

class Spans:
    """The benchmark's host spans around calls into the program: with
    tracing on, each is a ``TraceAnnotation`` named ``bench:<name>`` in the
    profiler's trace, so idle gaps on the device can be attributed to it."""

    def __init__(self):
        self.tracing = False

    def span(self, name: str):
        return _Span(self, name)


class _Span:
    def __init__(self, owner: Spans, name: str):
        self.owner, self.name, self.ann = owner, name, None

    def __enter__(self):
        if self.owner.tracing:
            import jax
            self.ann = jax.profiler.TraceAnnotation("bench:" + self.name)
            self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False

