"""Process start-up: platform forcing, the device table, the compile cache,
the kernel modules' import, the start-up record.

Two ways the program runs. On a TPU host JAX picks the chip by default and
one process owns it. Everywhere else (tests, the driver's multichip
dry-run) the caller pins `JAX_PLATFORMS=cpu`, and `force_cpu` adds the
virtual-device count an emulated mesh needs; it must run before any
backend initializes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import logging
import os
import re
import threading
import time
from typing import Optional

_COUNT_FLAG = "--xla_force_host_platform_device_count"

_log = logging.getLogger("transmogrifai_tpu.platform")

#: the checkout (parent of the package directory) — the default compile
#: cache lives under it so every process of one checkout shares one path
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: LRU cap of the cache this module places. The chip tool copies the
#: checkout, ignored files included, and refuses a copy over 256 MiB; a
#: full Tier-1 run leaves ~45 MB here.
_CACHE_MAX_BYTES = 128 << 20

#: the directory enable_compilation_cache last settled on (None = cache
#: disabled / not yet configured) — serve --prewarm-only reports it
_cache_dir: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Published per-chip peaks of one accelerator generation."""

    bf16_flops: float        # dense bf16 matmul peak, FLOP/s
    int8_ops: float          # int8 peak, OP/s
    hbm_bytes_per_s: float   # HBM bandwidth
    hbm_bytes: int           # HBM capacity
    vmem_bytes: int          # physical VMEM per TensorCore
    source: str


#: THE peaks table: every roofline / MFU denominator and every VMEM
#: budget reads it, keyed by the exact `jax.devices()[0].device_kind`
#: string. A TPU that is not listed is an error, never a default — add a
#: row with its source.
DEVICE_SPECS = {
    "TPU v5 lite": DeviceSpec(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
        hbm_bytes=16 * 10 ** 9, vmem_bytes=128 << 20,
        source='Google Cloud documentation, "TPU v5e" (197 TFLOP/s '
               "bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s); VMEM "
               "128 MiB per TensorCore is this repo's round-5 figure, "
               "not confirmed by a compiler message (PERF.md, PR 21)"),
}


def device_spec(device_kind: Optional[str] = None) -> Optional[DeviceSpec]:
    """Peaks of `device_kind`, or of the default backend's first device
    when omitted. None off-TPU (a CPU run has no roof to report); an
    unknown TPU kind raises."""
    if device_kind is None:
        import jax
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            return None
        device_kind = dev.device_kind
    spec = DEVICE_SPECS.get(device_kind)
    if spec is None:
        raise LookupError(
            f"no DEVICE_SPECS row for device kind {device_kind!r}; add "
            f"its published peaks (with source) to "
            f"transmogrifai_tpu/utils/platform.py")
    return spec


def startup_record() -> dict:
    """Where this process's start-up went, from the package's import to
    its first finished job (`validate()`, `train()`, `score()`): the six
    `startup_*_s` seconds that add up to `first_contact_s`, reaching the
    device in two by the instant the backend came up
    (`startup_backend_up_s`, `startup_first_dispatch_s`, and the CPU
    seconds of the first, `startup_backend_up_cpu_s`), the count of
    programs loaded or compiled, and the per-program rows (`fun_name`,
    trace, lower, load or compile seconds, `cache_hit`), slowest first.
    Always on; read it after one job. The ledger is utils/tracing's
    `tracker` (RecompileTracker.startup_record has every field)."""
    from .tracing import tracker
    return tracker.startup_record()


#: what a process imports before its first Pallas kernel can be traced
_KERNEL_MODULES = ("jax.experimental.pallas", "jax.experimental.pallas.tpu")


def prefetch_kernel_modules() -> Optional[threading.Thread]:
    """Import jax's Pallas modules on a daemon thread while the main thread
    reaches its device; the thread, or None where none was started.

    The import is 1.2 s on the v5e's host (0.8 s of it compiling modules to
    bytecode, 0.7 s under the GPU flavour of Mosaic that `pallas_call`
    imports on every backend) and the main thread pays it at its first
    kernel (`pallas_hist.available()`) unless it is done by then. The
    package starts this as the LAST act of its import, so nothing of the
    package's own import runs beside it and what follows is the backend's
    initialisation, 7-13 s that the main thread spends outside Python. The
    thread's interval goes to the start-up ledger (`startup_record()`'s
    `kernel_import_s`, and `kernel_import_cpu_s` the thread's own CPU
    seconds of it; it lies under `startup_reach_device_s`, not in the six
    seconds): 1.7-1.9 s beside the main thread, and in 3 of 14 measured
    runs as long as the backend took to come up (PERF.md, PR 34).

    A main thread that asks for one of these modules meanwhile waits on that
    module's import lock. Where two threads' imports wait on each other
    Python lets one go on with a module half made; that takes a cycle
    across the two, and there is none to have: jax is imported whole before
    the thread starts, and nothing that jax's core or a backend imports
    later reaches back into jax.experimental.pallas. An import that fails
    here is left for the main thread's own to raise.

    Nothing is started where the kernels cannot run: JAX pinned to a
    platform other than the TPU (the tests, CPU serving), no libtpu
    installed (a laptop, a GPU host), or `TMOG_NO_PALLAS`."""
    import jax
    from ..ops import pallas_hist
    from .tracing import tracker

    pinned = (jax.config.jax_platforms or "").strip().lower()
    if (not pallas_hist.enabled() or (pinned and "tpu" not in pinned)
            or importlib.util.find_spec("libtpu") is None):
        return None

    def load():
        start, cpu = time.time(), time.thread_time()
        try:
            for name in _KERNEL_MODULES:
                importlib.import_module(name)
        except Exception:       # the main thread's own import reports it
            pass
        tracker.mark_kernel_import(start, time.time(),
                                   time.thread_time() - cpu)

    thread = threading.Thread(target=load, name="tmog-kernel-imports",
                              daemon=True)
    thread.start()
    return thread


def compile_cache_dir() -> Optional[str]:
    """Active persistent-compilation-cache directory, or None when the
    cache is disabled."""
    return _cache_dir


def force_cpu(n_devices: int = 8) -> None:
    """Force the CPU backend with >= n_devices virtual devices.

    Safe to call multiple times; raises nothing if backends are already
    initialized (callers assert on the device count they actually got).
    Must run before jax.devices()/device_put/jit execution.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(re.escape(_COUNT_FLAG) + r"=(\d+)", flags)
    if m:
        if int(m.group(1)) < n_devices:
            flags = flags.replace(m.group(0), f"{_COUNT_FLAG}={n_devices}")
            os.environ["XLA_FLAGS"] = flags
    else:
        os.environ["XLA_FLAGS"] = (flags + f" {_COUNT_FLAG}={n_devices}").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        # Backends already initialized — nothing safe to change; the caller's
        # device-count assert will report what is actually available.
        pass
    # the import-time call may have run before the platform was pinned;
    # settle the default cache on its cpu leaf before the first compile
    enable_compilation_cache()


def enable_compilation_cache() -> None:
    """Settle XLA's persistent compilation cache, by precedence:

    1. `JAX_COMPILATION_CACHE_DIR` set: JAX already reads it — the
       directory is left exactly as the caller placed it.
    2. `TMOG_COMPILE_CACHE_DIR` set (the serve prewarm contract,
       docs/serving.md): that directory; `0`/`off` disables the cache.
    3. otherwise `<checkout>/.jax_cache/<cpu|tpu>`: a fixed path (the
       path is part of the cache key), git-ignored, one leaf per
       platform so CPU-pinned and chip processes never load each
       other's host executables.

    The directory must be settled before the process's FIRST compile:
    jax initializes its cache singleton on first use and ignores a later
    re-point. AutoML DAGs are many small programs, so every compile is
    cached regardless of its size or compile time.
    """
    global _cache_dir
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env_dir:
        loc = env_dir
    else:
        loc = os.environ.get("TMOG_COMPILE_CACHE_DIR", "").strip()
        if loc.lower() in ("0", "off"):
            _cache_dir = None
            _log.info("persistent compile cache: DISABLED (opt-out)")
            return
        if not loc:
            pinned = (jax.config.jax_platforms or "").strip().lower()
            loc = os.path.join(_CHECKOUT, ".jax_cache",
                               "cpu" if pinned == "cpu" else "tpu")
        jax.config.update("jax_compilation_cache_dir", loc)
        jax.config.update("jax_compilation_cache_max_size",
                          _CACHE_MAX_BYTES)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # A Mosaic body is serialised into its custom call WITH its operations'
    # locations, by default the ten innermost frames of the stack that
    # traced them, so an edit that moved a line of a CALLER (validators.py,
    # glm_sweep.py, models/trees.py) recompiled every kernel under it once
    # a machine, two minutes a tree program (ROADMAP.md S2 (1)). ONE frame,
    # the operation's own: the checkout's path is still in a kernel's key,
    # and so are the lines of the kernel's own file. (Not
    # `jax_include_full_tracebacks_in_locations` off, which also drops the
    # frame's FUNCTION name, and the chip's compiler names a Mosaic custom
    # call after it: `glm_moments.4`, `_route_hist_pallas_jit.9` would read
    # `tpu_custom_call.N` in every trace — PERF.md §6, PR 56.)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    if _cache_dir != loc:
        _cache_dir = loc
        _log.info("persistent compile cache: ACTIVE at %s", loc)
