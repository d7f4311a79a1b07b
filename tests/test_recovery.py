"""Warm-state recovery chaos suite (engine/shadow.py + the continuous
supervisor's restore path).

The bar, on top of tests/test_faults.py's cold-recovery guarantees:
  * chaos MATRIX — a crash at every fault point (admission / prefill /
    decode_launch / fetch / shadow_copy) × {warm, cold}: greedy output
    stays bit-identical to a fault-free run in EVERY cell, and the warm
    cells re-prefill only the partial tail block
    (dli_recovery_tokens_recomputed_total < block_size per request)
    while the cold cells recompute the whole sequence;
  * crash DURING restore (double fault): the supervisor contains the
    second crash, retries the restore, and the output is still
    bit-identical;
  * graceful drain persists the shadow to --restore-dir and a fresh
    engine restores it — the respawn serves the old prompt set with a
    warm block-prefix cache (the router's rolling-restart handoff);
  * the shadow store itself: content-keyed chains, LRU cascade
    eviction, bounded copier backpressure (drops, never blocks), and a
    crash-consistent (atomic-rename) on-disk format;
  * wedge-driven ejection: /ready flips 503 (reason "wedged") while an
    abandoned deadline-overrun call exceeds --wedge-unready, and
    recovers when the call drains — dli_engine_wedged tracks it.

Deterministic like the rest of the chaos tier: counter triggers, no wall
clock (marker `chaos`, never `slow`).
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from distributed_llm_inference_tpu import EngineConfig, get_model_config
from distributed_llm_inference_tpu.engine.continuous import ContinuousEngine
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.engine.shadow import ShadowStore
from distributed_llm_inference_tpu.serving.server import InferenceServer
from distributed_llm_inference_tpu.utils import faults

pytestmark = pytest.mark.chaos

BS = 8  # kv_block_size for every fleet here
POOL = 48
PROMPT = "the quick brown fox jumps over the"  # 27 ids, NOT a BS multiple


@pytest.fixture(autouse=True)
def _always_disarm():
    faults.disarm()
    yield
    faults.disarm()


@pytest.fixture(scope="module")
def engine():
    cfg = get_model_config("test-llama-tiny")
    return InferenceEngine(
        cfg,
        engine_cfg=EngineConfig(
            prefill_buckets=(32, 64), prefix_cache_entries=8
        ),
    )


@pytest.fixture(scope="module")
def solo(engine):
    return engine.generate(PROMPT, max_tokens=10, greedy=True, chat=False)


def _cont(engine, warm=True, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("chunk_steps", 4)
    kw.setdefault("restart_backoff_s", 0.01)
    kw.setdefault("kv_pool_blocks", POOL)
    kw.setdefault("kv_block_size", BS)
    return ContinuousEngine(engine, kv_shadow=warm, **kw)


def _ctr(engine, name):
    snap = engine.metrics.snapshot()
    return sum(
        s["value"] for s in snap.get(name, {}).get("series", [])
    )


def _get(base, path):
    try:
        with urllib.request.urlopen(base + path, timeout=15) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


# -- the chaos matrix ---------------------------------------------------------

# per-point trigger: late enough that the request is mid-flight with its
# prompt blocks already shadowed (decode_launch fires on the 4th launch
# so at least one healthy fetch lands first; the single-firing default
# keeps the recovery path itself fault-free)
_MATRIX_RULES = {
    "admission": dict(on_call=1),
    "prefill": dict(on_call=1),
    "decode_launch": dict(on_call=4),
    "fetch": dict(on_call=2),
    "shadow_copy": dict(on_call=1),
}


@pytest.mark.parametrize("warm", [True, False], ids=["warm", "cold"])
@pytest.mark.parametrize("point", sorted(_MATRIX_RULES))
def test_crash_matrix_warm_vs_cold(engine, solo, point, warm):
    """Crash at each fault point, warm (shadow on) vs cold (shadow off):
    output bit-identical in every cell; warm recomputes only the partial
    tail block, cold recomputes the whole sequence. The first (clean)
    serve populates the shadow, so even admission-time crashes — whose
    own blocks never filled — restore their prompt's chains."""
    cont = _cont(engine, warm=warm)
    try:
        r0 = cont.submit(PROMPT, max_tokens=10, greedy=True, chat=False)
        assert r0["response"] == solo["response"], r0
        if warm:
            assert cont._shadow.flush(10.0)
        base = _ctr(engine, "dli_recovery_tokens_recomputed_total")
        faults.arm([
            faults.FaultRule(point, "transient", **_MATRIX_RULES[point])
        ])
        r1 = cont.submit(PROMPT, max_tokens=10, greedy=True, chat=False)
        faults.disarm()
        if point == "shadow_copy" and not warm:
            # no shadow store => the point is never reached: the cell
            # degenerates to a fault-free serve (still bit-identical)
            assert cont.restarts_total == 0
            assert r1["response"] == solo["response"]
            return
        assert r1["status"] == "success", r1
        assert r1["response"] == solo["response"]
        assert r1["tokens_generated"] == solo["tokens_generated"]
        assert cont.restarts_total == 1
        assert cont.stats()["supervisor"]["ready"] is True
        recomputed = _ctr(
            engine, "dli_recovery_tokens_recomputed_total"
        ) - base
        if warm:
            # only the partial tail block (plus any salvage past the
            # last shadowed boundary) re-prefills
            assert 0 < recomputed < BS, recomputed
            assert cont.shadow_restored_total > 0
        else:
            # cold recovery recomputes the whole prompt(+salvage)
            assert recomputed > 2 * BS, recomputed
        # pool hygiene across the crash: everything not cached by the
        # prefix index is back on the free list
        st = cont.stats()["paged"]
        assert st["free_blocks"] + st["cached_blocks"] == POOL - 1
    finally:
        faults.disarm()
        cont.close()


def test_double_fault_crash_during_restore(engine, solo):
    """A SECOND crash inside the restore itself (shadow_copy at the
    'restore' tag) is contained like any scheduler crash: resources
    released, fleet rebuilt again, restore retried — greedy output still
    bit-identical, two restarts on the books."""
    cont = _cont(engine, warm=True)
    try:
        r0 = cont.submit(PROMPT, max_tokens=10, greedy=True, chat=False)
        assert r0["response"] == solo["response"]
        assert cont._shadow.flush(10.0)
        faults.arm([
            faults.FaultRule("decode_launch", "transient", on_call=4),
            faults.FaultRule(
                "shadow_copy", "transient", match="restore", on_call=1
            ),
        ])
        r1 = cont.submit(PROMPT, max_tokens=10, greedy=True, chat=False)
        faults.disarm()
        assert r1["status"] == "success", r1
        assert r1["response"] == solo["response"]
        assert cont.restarts_total == 2
        assert cont.shadow_restored_total > 0  # the retried restore
        assert cont.stats()["supervisor"]["ready"] is True
    finally:
        faults.disarm()
        cont.close()


def test_warm_beats_cold_on_recompute(engine, solo):
    """The acceptance inequality in one place: same crash, warm
    recomputes strictly fewer tokens than cold."""
    costs = {}
    for warm in (True, False):
        cont = _cont(engine, warm=warm)
        try:
            cont.submit(PROMPT, max_tokens=10, greedy=True, chat=False)
            if warm:
                cont._shadow.flush(10.0)
            base = _ctr(engine, "dli_recovery_tokens_recomputed_total")
            faults.arm([
                faults.FaultRule("decode_launch", "transient", on_call=4)
            ])
            r = cont.submit(PROMPT, max_tokens=10, greedy=True, chat=False)
            faults.disarm()
            assert r["response"] == solo["response"]
            costs[warm] = _ctr(
                engine, "dli_recovery_tokens_recomputed_total"
            ) - base
        finally:
            faults.disarm()
            cont.close()
    assert costs[True] < costs[False], costs


def test_warm_recovery_int8_pool():
    """The shadow rides the pool's pytree structure, so an int8 pool's
    KVQuant leaves (int8 blocks + float scales, different ranks) gather,
    persist, and restore through the same code — warm recovery stays
    bit-exact with KV quantization on."""
    cfg = get_model_config("test-llama-tiny", kv_quant="int8")
    eng = InferenceEngine(
        cfg,
        engine_cfg=EngineConfig(
            prefill_buckets=(32, 64), prefix_cache_entries=8
        ),
    )
    cont = _cont(eng, warm=True)
    try:
        r0 = cont.submit(PROMPT, max_tokens=10, greedy=True, chat=False)
        assert r0["status"] == "success"
        assert cont._shadow.flush(10.0)
        base = _ctr(eng, "dli_recovery_tokens_recomputed_total")
        faults.arm([
            faults.FaultRule("decode_launch", "transient", on_call=4)
        ])
        r1 = cont.submit(PROMPT, max_tokens=10, greedy=True, chat=False)
        faults.disarm()
        assert r1["status"] == "success", r1
        assert r1["response"] == r0["response"]
        assert cont.restarts_total == 1
        assert cont.shadow_restored_total > 0
        rec = _ctr(eng, "dli_recovery_tokens_recomputed_total") - base
        assert 0 < rec < BS, rec
    finally:
        faults.disarm()
        cont.close()


# -- drain persist / --restore-dir warm start --------------------------------

def test_drain_persists_and_restore_dir_warms_successor(engine, solo,
                                                        tmp_path):
    """The rolling-restart handoff: drain serializes the shadow (blocks
    + chain metadata) to --restore-dir; a successor engine restores it
    into its fresh pool before serving, so the old prompt set hits the
    block-prefix cache immediately — and greedy output is bit-identical
    across the drain->respawn boundary."""
    d = str(tmp_path / "restore")
    cont1 = _cont(engine, warm=True, restore_dir=d)
    try:
        r0 = cont1.submit(PROMPT, max_tokens=10, greedy=True, chat=False)
        assert r0["response"] == solo["response"]
        assert cont1._shadow.flush(10.0)
        assert cont1.drain(deadline_s=30.0) is True
    finally:
        cont1.close()
    cont2 = _cont(engine, warm=True, restore_dir=d)
    try:
        # the worker thread restores before serving; poll briefly
        t0 = time.time()
        while cont2.shadow_restored_total == 0 and time.time() - t0 < 10:
            time.sleep(0.02)
        assert cont2.shadow_restored_total > 0
        r1 = cont2.submit(PROMPT, max_tokens=10, greedy=True, chat=False)
        assert r1["status"] == "success"
        assert r1["response"] == solo["response"]
        # warm prefix cache: the mapped head covers every full prompt
        # block the predecessor shadowed
        assert r1.get("prefix_cached_tokens", 0) >= 2 * BS
        assert cont2.stats()["shadow"]["restored_blocks"] > 0
    finally:
        cont2.close()


def test_restore_dir_missing_or_invalid_starts_cold(engine, tmp_path):
    """A missing or corrupt persisted shadow is a cold start, never an
    error (warmth is an optimization)."""
    d = str(tmp_path / "nothing-here")
    cont = _cont(engine, warm=True, restore_dir=d)
    try:
        r = cont.submit(PROMPT, max_tokens=4, greedy=True, chat=False)
        assert r["status"] == "success"
        assert cont.shadow_restored_total == 0
    finally:
        cont.close()
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "shadow.npz").write_bytes(b"not an npz at all")
    cont = _cont(engine, warm=True, restore_dir=str(bad))
    try:
        r = cont.submit(PROMPT, max_tokens=4, greedy=True, chat=False)
        assert r["status"] == "success"
        assert cont.shadow_restored_total == 0
    finally:
        cont.close()


# -- shadow store units -------------------------------------------------------

def _mk_leaves(n, tag=0.0):
    """One fake stacked gather batch: two leaves of n blocks each."""
    return [
        np.full((n, 2, 3), tag, np.float32),
        np.full((n, 2), tag, np.float32),
    ]


def _put_sync(store, keys, tag=0.0, seq=0):
    assert store.put_async(keys, _mk_leaves(len(keys), tag), seq)
    assert store.flush(5.0)


def test_shadow_store_chains_and_select():
    s = ShadowStore(2, max_blocks=16)
    try:
        k1 = (1, 2)
        k2 = (1, 2, 3, 4)
        k3 = (9, 9)
        _put_sync(s, [k1, k2, k3], tag=1.0)
        assert s.has(k1) and s.has(k2) and s.has(k3)
        assert not s.has((5, 5))
        entries, leaves = s.select(10)
        assert [k for k, _ in entries] == sorted(
            [k1, k3, k2], key=len
        ) or len(entries) == 3
        assert set(leaves) == {k2, k3}
        # budget too small for the deep chain: the shorter chain still fits
        entries, leaves = s.select(1)
        assert len(entries) == 1
    finally:
        s.close()


def test_shadow_store_lru_cascade_eviction():
    s = ShadowStore(2, max_blocks=2)
    try:
        _put_sync(s, [(1, 2)])
        _put_sync(s, [(1, 2, 3, 4)])
        # inserting a new root evicts the LRU root (1,2) — and its child
        # cascades with it (a chain with a hole can never restore)
        _put_sync(s, [(7, 8)])
        assert s.has((7, 8))
        assert not s.has((1, 2)) and not s.has((1, 2, 3, 4))
        assert s.stats()["evicted"] >= 2
    finally:
        s.close()


def test_shadow_store_backpressure_drops_never_blocks():
    class _Slow:
        def __init__(self, arr):
            self._a = arr

        def __array__(self, dtype=None):
            time.sleep(0.3)
            return np.asarray(self._a, dtype=dtype)

    s = ShadowStore(2, max_blocks=16, max_pending=1)
    try:
        slow = [_Slow(leaf) for leaf in _mk_leaves(1)]
        assert s.put_async([(1, 1)], slow, 0)  # copier busy for 0.3s+
        t0 = time.time()
        while s._q and time.time() - t0 < 5:  # wait for the copier to
            time.sleep(0.005)  # pop the slow batch (now mid-transfer)
        t0 = time.time()
        s.put_async([(2, 2)], _mk_leaves(1), 0)  # queued (len 1)
        ok3 = s.put_async([(3, 3)], _mk_leaves(1), 0)  # full -> dropped
        assert time.time() - t0 < 0.25  # never blocked on the copier
        assert ok3 is False
        assert s.flush(10.0)
        assert s.stats()["dropped"] >= 1
        assert s.has((1, 1)) and s.has((2, 2)) and not s.has((3, 3))
    finally:
        s.close()


def test_shadow_store_save_load_round_trip(tmp_path):
    s = ShadowStore(2, max_blocks=16)
    try:
        _put_sync(s, [(1, 2), (1, 2, 3, 4), (9, 9)], tag=7.0, seq=42)
        assert s.save(str(tmp_path)) == 3
    finally:
        s.close()
    t = ShadowStore(2, max_blocks=16)
    try:
        assert t.load(str(tmp_path)) == 3
        assert t.has((1, 2, 3, 4)) and t.has((9, 9))
        entries, _ = t.select(10)
        data = dict(entries)
        np.testing.assert_array_equal(
            data[(1, 2)].leaves[0], np.full((2, 3), 7.0, np.float32)
        )
        assert data[(1, 2)].seq == 42
    finally:
        t.close()
    # wrong block size: refused, cold start
    u = ShadowStore(4, max_blocks=16)
    try:
        assert u.load(str(tmp_path)) == 0
    finally:
        u.close()


# -- wedge-driven readiness (satellite: router ejection signal) --------------

def test_wedge_flips_ready_503_until_the_call_drains():
    """An abandoned deadline-overrun device call past --wedge-unready
    flips /ready to 503 (reason 'wedged') while /health stays 200 — the
    router's probes eject the replica, and readmit it once the wedged
    call drains. dli_engine_wedged tracks the abandoned-call count."""
    import dataclasses

    cfg = get_model_config("test-llama-tiny")
    eng = InferenceEngine(
        cfg, engine_cfg=EngineConfig(prefill_buckets=(32, 64)),
    )
    # compile BEFORE the deadline arms, or the warmup itself would
    # overrun it and leave its own abandoned-call entry
    eng.generate("warm", max_tokens=2, greedy=True, chat=False)
    eng.engine_cfg = dataclasses.replace(
        eng.engine_cfg, request_deadline_s=0.3
    )
    server = InferenceServer(
        eng, host="127.0.0.1", port=0, wedge_unready_s=0.2
    )
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        faults.arm([
            faults.FaultRule("solo", "transient", wedge_s=2.5, times=1)
        ])
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps(
                {"prompt": "wedge me", "max_tokens": 4, "chat": False}
            ).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=15) as r:
                body = json.loads(r.read())
                code = r.status
        except urllib.error.HTTPError as e:
            code, body = e.code, json.loads(e.read())
        assert code == 503 and body["error_type"] == "timeout", body
        assert eng.max_wedged_age() is not None
        time.sleep(0.25)  # age past the 0.2s wedge-unready threshold
        code, body, hdrs = _get(base, "/ready")
        assert code == 503 and body["reason"] == "wedged", body
        assert hdrs.get("Retry-After")
        code, body, _ = _get(base, "/health")
        assert code == 200 and body["ready"] is False
        assert body["ready_reason"] == "wedged"
        assert _ctr(eng, "dli_engine_wedged") == 1
        # the wedge drains (the sleep ends, the daemon thread exits):
        # readiness recovers without a restart
        t0 = time.time()
        while eng.max_wedged_age() is not None and time.time() - t0 < 10:
            time.sleep(0.05)
        code, body, _ = _get(base, "/ready")
        assert code == 200 and body["ready"] is True
        assert _ctr(eng, "dli_engine_wedged") == 0
    finally:
        faults.disarm()
        server.shutdown()


def test_wedge_unready_zero_disables():
    cfg = get_model_config("test-llama-tiny")
    eng = InferenceEngine(
        cfg,
        engine_cfg=EngineConfig(
            prefill_buckets=(32, 64), request_deadline_s=0.2
        ),
    )
    server = InferenceServer(
        eng, host="127.0.0.1", port=0, wedge_unready_s=0.0
    )
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        with eng._wedged_lock:
            eng._wedged[object()] = {"what": "t", "since": time.monotonic()}
        time.sleep(0.05)
        code, body, _ = _get(base, "/ready")
        assert code == 200 and body["ready"] is True
    finally:
        with eng._wedged_lock:
            eng._wedged.clear()
        server.shutdown()


# -- pp warm-recovery seam (the shard_map shadow twins) -----------------------

@pytest.mark.slow
def test_pp_shadow_gather_restore_roundtrip(eight_devices):
    """The pipeline backend's layer-local shadow twins: restoring known
    block content into a pp=2-sharded pool and gathering it back is the
    identity — the seam that lets pp fleets recover WARM (the old
    follow-up: pp pools recovered cold)."""
    import jax.numpy as jnp

    from distributed_llm_inference_tpu import MeshConfig
    from distributed_llm_inference_tpu.runtime import create_engine

    eng = create_engine(
        "test-llama-tiny", mesh_cfg=MeshConfig(pp=2),
        engine_cfg=EngineConfig(
            prefill_buckets=(32, 64), prefix_cache_entries=4
        ),
    )
    be = eng.backend
    pool = be.init_paged_pool(9, BS)
    ids = jnp.asarray([3, 6, 2], jnp.int32)
    blocks = {
        k: jnp.asarray(
            np.random.RandomState(i).standard_normal(
                (3, v.shape[0]) + v.shape[2:]
            ),
            v.dtype,
        )
        for i, (k, v) in enumerate(pool.items())
    }
    pool = be.restore_shadow_blocks(pool, blocks, ids)
    back = be.gather_shadow_blocks(pool, ids)
    for k in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(back[k]), np.asarray(blocks[k])
        )


@pytest.mark.slow
def test_pp_fleet_recovers_warm(eight_devices):
    """End to end on the pp=2 mesh: the continuous fleet's shadow is
    ENABLED (the backend now carries the twins), and a mid-decode crash
    recovers warm — only the partial tail block re-prefills, greedy
    output bit-identical."""
    from distributed_llm_inference_tpu import MeshConfig
    from distributed_llm_inference_tpu.runtime import create_engine

    eng = create_engine(
        "test-llama-tiny", mesh_cfg=MeshConfig(pp=2),
        engine_cfg=EngineConfig(
            prefill_buckets=(32, 64), prefix_cache_entries=8
        ),
    )
    solo_pp = eng.generate(PROMPT, max_tokens=10, greedy=True, chat=False)
    cont = ContinuousEngine(
        eng, n_slots=2, chunk_steps=4, restart_backoff_s=0.01,
        kv_pool_blocks=POOL, kv_block_size=BS,
    )
    try:
        assert cont._shadow is not None  # the seam: pp shadows now
        r0 = cont.submit(PROMPT, max_tokens=10, greedy=True, chat=False)
        assert r0["response"] == solo_pp["response"]
        assert cont._shadow.flush(10.0)
        base = _ctr(eng, "dli_recovery_tokens_recomputed_total")
        faults.arm([
            faults.FaultRule("decode_launch", "transient", on_call=4)
        ])
        r1 = cont.submit(PROMPT, max_tokens=10, greedy=True, chat=False)
        faults.disarm()
        assert r1["status"] == "success", r1
        assert r1["response"] == solo_pp["response"]
        recomputed = _ctr(
            eng, "dli_recovery_tokens_recomputed_total"
        ) - base
        assert 0 < recomputed < BS, recomputed
        assert cont.shadow_restored_total > 0
    finally:
        faults.disarm()
        cont.close()
