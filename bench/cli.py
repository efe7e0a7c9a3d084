"""What every script of the benchmark does before it runs a cell: keep
JAX's compilation cache in the checkout, and refuse to run without the
chips the cell asks for."""
from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = ROOT / "bench_out"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def prepare(chips: int) -> dict:
    """Point the compile cache at the checkout, then return the device
    as JAX reports it. Exits non-zero with no accelerator, with fewer
    chips than asked, or on a chip with no entry in the peaks table."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # the TPU runtime logs to /tmp/tpu_logs unless told otherwise
    log_dir = OUT_DIR / "tpu_logs"
    log_dir.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(log_dir))
    import jax

    from . import manifest

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] == "cpu":
        raise SystemExit(f"bench: no accelerator ({info}); nothing was run")
    if info["count"] < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, found {info}")
    manifest.load_peaks(info["kind"])
    log(f"device: {info}")
    return info
