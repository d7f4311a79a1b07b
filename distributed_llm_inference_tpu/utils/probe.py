"""Device liveness probing for the /workers health sweep.

The reference's /workers actually polls each worker's /health over HTTP
with a 5 s timeout and reports online / offline / error
(/root/reference/orchestration.py:306-329). A mesh stage is an in-process
device slice, so the equivalent probe is a tiny timed device op: round-trip
one scalar through the device and report how long it took. A wedged device
(hung transfer queue, lost device) is reported "offline" after the timeout
instead of hanging the health endpoint.
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp


def probe_device(dev, timeout_s: float = 5.0, _op=None) -> dict:
    """One device's liveness: {"status": online|offline|error, ...}.

    online  -> includes probe_ms (scalar round-trip time)
    error   -> the op raised; includes the error string
    offline -> the op did not complete within timeout_s (probe thread is
               abandoned — it cannot be killed, but it is daemonic)
    """
    result: dict = {}

    def run():
        try:
            t0 = time.perf_counter()
            if _op is not None:
                _op()
            else:
                x = jax.device_put(jnp.int32(1), dev)
                jax.block_until_ready(x + 1)
            result.update(
                status="online",
                probe_ms=round((time.perf_counter() - t0) * 1e3, 2),
            )
        except Exception as e:  # noqa: BLE001 - health must not raise
            result.update(status="error", error=str(e)[:300])

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout_s)
    if not result:
        return {
            "status": "offline",
            "error": f"device probe timed out after {timeout_s:.1f}s",
        }
    return result


def device_summary() -> dict:
    """What this process runs on, as JAX reports it: the /health `device`
    field (and chip_smoke.py's last line)."""
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def device_memory(dev) -> dict:
    """One device's allocator counters for the /workers detail —
    `bytes_in_use` per device shows whether a sharded model really spread
    over the mesh. Empty where the backend reports none (CPU)."""
    stats = dev.memory_stats() or {}
    return {
        k: stats[k]
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
        if k in stats
    }
