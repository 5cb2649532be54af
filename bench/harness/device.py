"""The chip: refuse anything else, keep the compile cache in the checkout,
report the device as JAX sees it, count compilations."""
from __future__ import annotations

import os
import pathlib

#: Platforms the benchmark measures. The CPU is never one of them.
ACCELERATORS = ("tpu",)


class NoChip(SystemExit):
    """Raised (exit code 3) when JAX finds no accelerator or too few."""

    def __init__(self, msg: str):
        super().__init__(3)
        self.msg = msg

    def __str__(self):
        return self.msg


def require_chips(chips: int):
    """The devices the cell runs on, or ``NoChip``: no CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform not in ACCELERATORS:
        raise NoChip(f"no accelerator: JAX sees platform "
                     f"{devices[0].platform!r}; the benchmark never runs on "
                     f"the CPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices


def enable_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR`` or
    ``<checkout>/.jax_cache``, with every program cached, however fast it
    compiled and however small it is."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int | None:
    """Peak bytes in use on the fullest device, where the backend says."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts programs compiled or loaded from the cache (``programs``) and
    persistent-cache misses, from JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.programs = 0
        self.misses = 0
        self.compile_s = 0.0

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.programs += 1
                self.compile_s += duration

        mon.register_event_listener(on_event)
        mon.register_event_duration_secs_listener(on_duration)

    def snapshot(self) -> tuple[int, int]:
        return self.programs, self.misses
