"""Paths, the files the harness finds by name, and the device it runs on."""
from __future__ import annotations

import contextlib
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def peaks() -> dict:
    return load_json(BENCH / "peaks.json")


def use_program() -> None:
    """Put the system under test (``src/``) on the import path."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"bench: the program is not in this checkout ({src})")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def use_compile_cache() -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` where it is
    set, else ``.jax_cache`` at the root of the checkout, a fixed path."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_check(chips: int):
    """The first ``chips`` TPU devices; exits 2 naming what JAX found otherwise."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        print(f"bench: needs a TPU; JAX found {first.platform} "
              f"({first.device_kind}) x{len(devices)}", file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} chips; JAX sees {len(devices)}",
              file=sys.stderr)
        raise SystemExit(2)
    return devices[:chips]


def span(name: str, on: bool):
    """A host span in the profiler's trace while tracing, else nothing."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)
