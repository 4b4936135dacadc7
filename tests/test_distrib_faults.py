"""Fault-tolerance & elasticity drills for the cluster runtime.

Covers the PR-7 robustness layer: TCP transport with authkey rotation,
reconnect-with-backoff, heartbeat liveness, per-task deadlines with
bounded retry, elastic join/drain (including the previously-untested
clean scale-down path), degrade-to-local, and the seeded chaos harness
(message drop/delay/duplication, babble, hang, refused rejoin).
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.distrib import (ChaosPlan, ChaosWire, ClusterRuntime,
                           ElasticController, ElasticPolicy, new_authkey)
from repro.distrib import chaos
from repro.distrib.transport import (AuthenticationError,
                                     authed_connect)


def _nap(seconds):
    """Picklable sleep task (``time.sleep`` itself is a builtin, which
    the code-object serializer rightly refuses)."""
    time.sleep(seconds)


def _pfor_roundtrip(rt, n=64, **kw):
    """One pfor round; asserts the merged result is exactly correct."""
    x = np.arange(n, dtype=np.float64)
    out = np.zeros(n)

    def body(lo, hi):
        for i in range(lo, hi):
            out[i] = x[i] * 2.0 + 1.0

    rt.pfor_shards(body, 0, n, written=("out",), sliceable=("x",), **kw)
    np.testing.assert_allclose(out, x * 2.0 + 1.0, atol=1e-8)


def _poll(pred, timeout_s=8.0, interval_s=0.05, desc="condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(interval_s)
    raise AssertionError(f"timed out waiting for {desc}")


# -- TCP transport ---------------------------------------------------------

def test_tcp_transport_basic():
    with ClusterRuntime(workers=2, transport="tcp",
                        hb_interval_s=0.2) as rt:
        assert rt.address is not None and rt.address[1] > 0
        assert rt.get(rt.submit(lambda a, b: a + b, 20, 22),
                      timeout=10.0) == 42
        _pfor_roundtrip(rt)
        st = rt.stats()
        assert st["transport"] == "tcp"
        assert st["workers"] == 2


def test_tcp_authkey_rotation_refuses_stale_key():
    with ClusterRuntime(workers=2, transport="tcp",
                        hb_interval_s=0.2) as rt:
        stale = rt.listener.authkey
        fresh = rt.rotate_authkey(new_authkey())
        assert fresh != stale
        # a client still holding the pre-rotation key fails the HMAC
        # challenge and is counted, never served
        with pytest.raises((AuthenticationError, EOFError, OSError)):
            authed_connect(rt.address, stale)
        _poll(lambda: rt.listener.auth_failures >= 1,
              desc="auth failure counter")
        # connected workers learned the new key in-band: severing a
        # socket forces a reconnect that must authenticate with it
        wid = chaos.drop_conn(rt)
        assert wid is not None
        _poll(lambda: rt.stats()["faults"].get("rejoins", 0) >= 1,
              desc="rejoin after rotation")
        _pfor_roundtrip(rt)
        assert rt.workers_alive() == 2


def test_tcp_reconnect_with_backoff_after_drop():
    with ClusterRuntime(workers=2, transport="tcp",
                        hb_interval_s=0.2) as rt:
        assert chaos.drop_conn(rt) is not None
        _poll(lambda: rt.stats()["faults"].get("rejoins", 0) >= 1,
              desc="worker rejoin")
        st = rt.stats()
        assert st["faults"].get("conn_lost", 0) >= 1
        assert st["worker_deaths"] == 0   # a blip is not a death
        _pfor_roundtrip(rt)


def test_tcp_refused_reconnect_fences_worker():
    with ClusterRuntime(workers=2, transport="tcp", hb_interval_s=0.2,
                        reconnect_grace_s=0.5) as rt:
        with rt._lock:
            wid = next(iter(rt._handles))
        chaos.refuse_reconnect(rt, wid)
        assert chaos.drop_conn(rt, wid) == wid
        # the denied worker exits; the head reaps it when the grace
        # window expires, then respawns a replacement
        _poll(lambda: rt.stats()["worker_deaths"] >= 1,
              desc="fenced worker declared dead")
        _poll(lambda: rt.workers_alive() == 2, desc="respawn")
        st = rt.stats()
        assert st["faults"].get("fenced", 0) >= 1
        _pfor_roundtrip(rt)


def test_tcp_external_worker_joins_fleet():
    with ClusterRuntime(workers=1, transport="tcp",
                        hb_interval_s=0.2) as rt:
        host, port = rt.address
        env = dict(os.environ)
        src = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src")
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.Popen(
            [sys.executable, "-m", "repro.distrib.worker",
             "--connect", f"{host}:{port}",
             "--authkey", rt.listener.authkey.hex(), "--hb", "0.2"],
            env=env)
        try:
            _poll(lambda: rt.workers_alive() == 2, timeout_s=30.0,
                  desc="external worker join")
            _poll(lambda: len(rt._views()) == 2, timeout_s=10.0,
                  desc="joined worker profiled")
            assert rt.stats()["faults"].get("joins", 0) >= 1
            _pfor_roundtrip(rt)
        finally:
            proc.terminate()
            proc.wait(timeout=10)


# -- active liveness -------------------------------------------------------

def test_heartbeat_expiry_reaps_hung_worker():
    with ClusterRuntime(workers=2, hb_interval_s=0.1,
                        hb_miss_budget=3) as rt:
        assert chaos.hang(rt, seconds=20.0,
                          silence_heartbeat=True) is not None
        _poll(lambda: rt.stats()["faults"].get("hb_expired", 0) >= 1,
              desc="heartbeat expiry")
        _poll(lambda: rt.stats()["worker_deaths"] >= 1,
              desc="hung worker declared dead")
        _poll(lambda: rt.workers_alive() == 2, desc="respawn after hang")
        _pfor_roundtrip(rt)


def test_task_deadline_retries_then_degrades():
    # hang the whole fleet with heartbeats still flowing: only the
    # per-task deadline can recover. Retries burn the budget on the
    # still-hung fleet, then each chunk degrades to local execution.
    with ClusterRuntime(workers=2, max_attempts=2) as rt:
        with rt._lock:
            wids = list(rt._handles)
        for wid in wids:
            assert chaos.hang(rt, wid, seconds=30.0,
                              silence_heartbeat=False) == wid
        _pfor_roundtrip(rt, n=16, deadline_s=0.3)
        st = rt.stats()
        assert st["faults"].get("deadline_expired", 0) >= 1
        assert st["faults"].get("degraded_chunks", 0) >= 1
        assert st["faults"].get("retries", 0) >= 1


def test_get_timeout_names_task_worker_and_heartbeat_age():
    with ClusterRuntime(workers=1) as rt:
        ref = rt.submit(_nap, 1.5)
        with pytest.raises(TimeoutError) as ei:
            rt.get(ref, timeout=0.2)
        msg = str(ei.value)
        assert "task" in msg and "worker" in msg
        assert "heartbeat" in msg or "never dispatched" in msg
        assert rt.get(ref, timeout=10.0) is None   # still completes


def test_wait_on_timeout_raise_names_pending_tasks():
    with ClusterRuntime(workers=1) as rt:
        ref = rt.submit(_nap, 1.0)
        ready, pending = rt.wait([ref], timeout=0.1)   # default: ray
        assert ready == [] and pending == [ref]
        with pytest.raises(TimeoutError) as ei:
            rt.wait([ref], timeout=0.1, on_timeout="raise")
        assert "pending" in str(ei.value) and "task" in str(ei.value)
        rt.get(ref, timeout=10.0)


# -- degradation -----------------------------------------------------------

def test_degrades_to_local_when_fleet_collapses():
    with ClusterRuntime(workers=2, respawn=False) as rt:
        while rt.kill_worker() is not None:
            pass
        _poll(lambda: rt.workers_alive() == 0, desc="fleet collapse")
        _pfor_roundtrip(rt)   # runs in-process on the head
        st = rt.stats()
        assert st["faults"].get("degraded_local_runs", 0) >= 1


# -- chaos harness ---------------------------------------------------------

def test_malformed_message_is_counted_not_swallowed():
    with ClusterRuntime(workers=2) as rt:
        assert chaos.babble(rt) is not None
        _poll(lambda: rt.stats()["faults"].get("malformed_msgs", 0) >= 1,
              desc="malformed message counter")
        _pfor_roundtrip(rt)   # the receiver thread survived


def test_chaos_dropped_blob_recovers_via_reship():
    plan = ChaosPlan(seed=7, drop_p=1.0, drop_kinds=("blob",),
                     max_drops=1)
    with ClusterRuntime(workers=2, chaos=plan) as rt:
        _pfor_roundtrip(rt)
        st = rt.stats()
        assert plan.dropped == 1
        assert st["faults"].get("blob_missing", 0) >= 1
        assert st["resubmits"] >= 1


def test_chaos_delay_preserves_message_order():
    sent = []

    class FakeConn:
        def send(self, msg):
            sent.append(msg[0])

        def close(self):
            pass

    plan = ChaosPlan(seed=3, delay_s=0.1, delay_kinds=("blob",))
    wire = ChaosWire(FakeConn(), plan, peer=0)
    wire.send(("blob", 1, b"skel", {}))
    wire.send(("task", 9, {}))   # zero-delay, but must stay FIFO
    _poll(lambda: len(sent) == 2, desc="delayed drain")
    assert sent == ["blob", "task"]
    assert plan.delayed == 1
    wire.close()


def test_chaos_plan_is_deterministic_per_seed():
    def decisions(seed):
        plan = ChaosPlan(seed=seed, drop_p=0.5, dup_p=0.3)
        sent = []

        class FakeConn:
            def send(self, msg):
                sent.append(msg)

            def close(self):
                pass

        wire = ChaosWire(FakeConn(), plan, peer=1)
        for i in range(100):
            wire.send(("ping", i))
        return sent

    a, b = decisions(11), decisions(11)
    assert a == b                       # bit-identical replay
    assert decisions(12) != a           # and actually seed-dependent


# -- elastic membership ----------------------------------------------------

def test_drain_scales_down_cleanly_preserving_objects():
    with ClusterRuntime(workers=3) as rt:
        ref = rt.submit(lambda: np.ones((128, 128)))   # > INLINE_MAX
        rt.wait([ref], timeout=10.0)
        owner = rt.plane.meta(ref.oid).owner
        assert owner is not None
        assert rt.drain_worker(owner) == owner
        _poll(lambda: rt.workers_alive() == 2, desc="clean drain")
        st = rt.stats()
        assert st["worker_deaths"] == 0          # drain is not a death
        assert st["faults"].get("drains", 0) >= 1
        # the drained worker's object survived the scale-down
        np.testing.assert_allclose(rt.get(ref, timeout=10.0),
                                   np.ones((128, 128)))
        _pfor_roundtrip(rt)


def test_scale_to_shrinks_and_grows():
    with ClusterRuntime(workers=2) as rt:
        rt.scale_to(1)
        _poll(lambda: rt.workers_alive() == 1, desc="shrink to 1")
        rt.scale_to(3)
        _poll(lambda: rt.workers_alive() == 3, desc="grow to 3")
        assert len(rt._views()) == 3
        _pfor_roundtrip(rt)


def test_join_prewarms_blobs_and_rebalances_chunks():
    with ClusterRuntime(workers=1) as rt:
        _pfor_roundtrip(rt)   # warm the persistent body blob
        _pfor_roundtrip(rt)
        wid = rt.add_worker()
        assert wid is not None
        wh = rt._handle_for(wid)
        assert wh.blobs, "joining worker was not pre-warmed"
        for _ in range(3):
            _pfor_roundtrip(rt)
        by_worker = rt.stats()["chunks_executed_by_worker"]
        assert wid in by_worker and by_worker[wid] >= 1, \
            f"joined worker got no chunk share: {by_worker}"
        assert len(by_worker) >= 2


def test_elastic_controller_drives_cluster_runtime():
    with ClusterRuntime(workers=1) as rt:
        ctrl = ElasticController(rt, ElasticPolicy(
            min_workers=1, max_workers=3, step=1))
        refs = [rt.submit(_nap, 0.3) for _ in range(8)]
        deadline = time.monotonic() + 20.0
        while rt.workers_alive() < 2 and time.monotonic() < deadline:
            ctrl.tick()
            time.sleep(0.05)
        assert rt.workers_alive() >= 2, ctrl.decisions
        assert ctrl.decisions, "controller never decided to scale"
        rt.get(refs, timeout=30.0)
        # drained back down once the queue empties
        for _ in range(40):
            ctrl.tick()
            if len(rt._views()) == 1:
                break
            time.sleep(0.05)
        _poll(lambda: rt.workers_alive() == 1, desc="scale back down")


def _busy_worker(rt):
    with rt._lock:
        busy = [t.wid for t in rt._tasks.values()
                if not t.finished and t.dispatched_at is not None]
    return busy[0] if busy else None


def test_elastic_controller_sizes_from_live_workers_while_one_drains():
    with ClusterRuntime(workers=3) as rt:
        ref = rt.submit(_nap, 2.0)
        _poll(lambda: _busy_worker(rt) is not None, desc="task dispatched")
        wid = _busy_worker(rt)
        assert rt.drain_worker(wid) == wid
        # the draining worker is still alive (finishing its task) but
        # no longer counts: at min_workers the controller holds steady
        # instead of shrinking a second worker
        ctrl = ElasticController(rt, ElasticPolicy(min_workers=2,
                                                   max_workers=4),
                                 depth_fn=lambda: 0)
        assert rt.workers_alive() == 3
        assert ctrl.tick() == 2
        assert ctrl.decisions == []
        rt.get(ref, timeout=30.0)
        _poll(lambda: rt.workers_alive() == 2, desc="drain completes")
        assert rt.stats()["worker_deaths"] == 0
