"""Host + device memory gauges (best-effort, dependency-free).

Host RSS comes from /proc/self/status (Linux) with a resource.getrusage
fallback; device memory from ``Device.memory_stats()`` where the backend
exposes it (the CPU backend does not — absent keys are simply omitted
from the gauges).  Peak watermarks are tracked process-wide so a
trace's last iteration record carries the high-water mark even when
individual snapshots move around.
"""

from __future__ import annotations

from typing import Any, Dict

_peaks = {"host_rss_mb": 0.0, "dev_mb": 0.0}


def host_rss_mb() -> float:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return float(line.split()[1]) / 1024.0
    except OSError:
        pass
    try:  # pragma: no cover - non-Linux fallback
        import resource

        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    except Exception:  # pragma: no cover
        return 0.0
    return 0.0


def device_memory_mb() -> Dict[str, float]:
    """{'dev_mb': in-use, 'dev_peak_mb': backend peak} when exposed.
    Only queried once jax is already imported — never triggers backend
    initialization on its own."""
    import sys

    if "jax" not in sys.modules:
        return {}
    jax = sys.modules["jax"]
    try:
        ms = jax.local_devices()[0].memory_stats()
    except Exception:
        return {}
    if not ms or "bytes_in_use" not in ms:
        return {}
    out = {"dev_mb": round(ms["bytes_in_use"] / 1e6, 1)}
    if "peak_bytes_in_use" in ms:
        out["dev_peak_mb"] = round(ms["peak_bytes_in_use"] / 1e6, 1)
    return out


def memory_gauges() -> Dict[str, Any]:
    """Combined host+device snapshot used on every iteration record."""
    out: Dict[str, Any] = {"host_rss_mb": round(host_rss_mb(), 1)}
    out.update(device_memory_mb())
    for k in ("host_rss_mb", "dev_mb"):
        if k in out and out[k] > _peaks[k]:
            _peaks[k] = out[k]
    return out


def peaks() -> Dict[str, float]:
    return dict(_peaks)
