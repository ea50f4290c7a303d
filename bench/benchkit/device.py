"""The chip: presence check, description, peaks, memory, compile count."""
from __future__ import annotations

import json
import os
import time
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def require_tpu(chips: int):
    """The devices of the cell; raises :class:`NoChip` without a TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform!r} "
                     f"({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; a kind that is not
    in the table is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def describe(devices) -> dict:
    """``device`` of the result line; the peak is the fullest chip's."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak}


def process_start() -> float:
    """``time.perf_counter()`` reading of the moment this process started
    (from ``/proc``; the import time of this module where that is
    unreadable)."""
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        with open("/proc/self/stat") as fh:
            start = int(fh.read().rsplit(")", 1)[1].split()[19]) / ticks
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        return time.perf_counter() - (uptime - start)
    except (OSError, ValueError, IndexError):
        return _IMPORTED


_IMPORTED = time.perf_counter()


class CompileCounter:
    """Counts the executables made while armed: every one that JAX's
    dispatch asked for (``made``), those loaded from the persistent
    compilation cache (``loaded``), and the rest, compiled by the backend
    (``compiled``, named in ``compiled_names``).  JAX reports the first
    around its look into the cache, and the look's hit inside it, so a
    load counts in ``made`` too."""

    def __init__(self):
        import jax.monitoring as mon

        self.armed = False
        self.made = 0
        self.loaded = 0
        self.compiled_names = []
        self._hit = False
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    @property
    def compiled(self) -> int:
        return self.made - self.loaded

    def note(self) -> str:
        return (f"compiles_in_window {self.compiled} (executables made "
                f"{self.made}, loaded from the persistent cache "
                f"{self.loaded}; compiled: "
                f"{' '.join(self.compiled_names) or '-'})")

    def _on_duration(self, event, duration, fun_name="?", **_):
        if event != BACKEND_COMPILE_EVENT:
            return
        hit, self._hit = self._hit, False
        if self.armed:
            self.made += 1
            if not hit:
                self.compiled_names.append(str(fun_name))

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self._hit = True
            if self.armed:
                self.loaded += 1


def seed_key(seed: int):
    """A PRNG key from any whole seed, 64-bit ones included."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def setup_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``<root>/.jax_cache``, a fixed path inside the
    checkout.  Every program is kept, however quick its compile, so a
    second run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
