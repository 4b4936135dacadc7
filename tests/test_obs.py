"""Observability plane tests: span recorder (nesting, threads,
cross-thread tokens, ring bound), clock-offset alignment, Chrome-trace
export schema, the summarize analyzer, and the unified metrics registry
backing the legacy stats()/telemetry() surfaces."""

import glob
import json
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.core import backends
from repro.core.compiler import compile_kernel, optimize
from repro.core.multiversion import PHASES
from repro.distrib import ClusterRuntime
from repro.distrib.cluster import _WorkerHandle
from repro.obs import summarize
from repro.obs.metrics import Counter, DictMetric, MetricsRegistry
from repro.obs.spans import SpanRecorder


@pytest.fixture
def traced():
    """Tracing on with a clean ring; restores the dark default after."""
    was = obs.enabled()
    obs.enable()
    obs.recorder().clear()
    yield obs.recorder()
    obs.recorder().clear()
    if not was:
        obs.disable()


# ---------------------------------------------------------------------------
# span recorder
# ---------------------------------------------------------------------------

def test_span_dark_by_default_costs_nothing():
    assert not obs.enabled()
    rec = obs.recorder()
    n = len(rec)
    # the no-op context manager is a shared singleton and records nothing
    s1 = obs.span("x", cat="t")
    s2 = obs.span("y", cat="t")
    assert s1 is s2
    with s1:
        pass
    assert obs.begin("x") is None
    obs.end(None)          # safe on the dark-path token
    assert len(rec) == n


def test_span_nesting_and_args(traced):
    with obs.span("outer", cat="t", round=1):
        with obs.span("inner", cat="t", task=7):
            time.sleep(0.001)
    evs = {e.name: e for e in traced.events()}
    assert set(evs) == {"outer", "inner"}
    # inner closed first and nests strictly inside outer
    assert evs["outer"].t0 <= evs["inner"].t0
    assert evs["inner"].t1 <= evs["outer"].t1
    assert evs["inner"].dur > 0
    assert evs["outer"].args == {"round": 1}
    assert evs["inner"].args == {"task": 7}
    # both ran on the same (main) thread → same track
    assert evs["outer"].tid == evs["inner"].tid


def test_spans_from_threads_get_distinct_tracks(traced):
    def work():
        with obs.span("worker_side", cat="t"):
            time.sleep(0.001)

    with obs.span("main_side", cat="t"):
        th = threading.Thread(target=work, name="helper")
        th.start()
        th.join()
    evs = {e.name: e for e in traced.events()}
    assert evs["main_side"].tid != evs["worker_side"].tid
    names = traced.track_names()
    assert any(v.startswith("head:") for v in names.values())


def test_cross_thread_token_and_idempotent_end(traced):
    tok = obs.begin("inflight", cat="t", task=3)
    done = threading.Event()

    def finisher():
        obs.end(tok, wid=1)
        done.set()

    threading.Thread(target=finisher).start()
    assert done.wait(5.0)
    obs.end(tok, wid=9)      # second end: a no-op, not a second event
    evs = [e for e in traced.events() if e.name == "inflight"]
    assert len(evs) == 1
    assert evs[0].args == {"task": 3, "wid": 1}


def test_ring_buffer_bounds_memory():
    rec = SpanRecorder(capacity=16)
    assert rec.capacity == 16
    for i in range(40):
        rec.record(f"e{i}", "t", 0.0, 1.0)
    assert len(rec) == 16
    assert rec.dropped == 24
    # oldest events were the ones evicted
    assert [e.name for e in rec.events()][0] == "e24"
    rec.clear()
    assert len(rec) == 0 and rec.dropped == 0


def test_enable_resizes_only_on_change(traced):
    rec = obs.recorder()
    rec.record("keep", "t", 0.0, 1.0)
    obs.enable()                     # same capacity: ring untouched
    assert [e.name for e in obs.recorder().events()] == ["keep"]


# ---------------------------------------------------------------------------
# clock alignment
# ---------------------------------------------------------------------------

def test_worker_clock_offset_takes_min_over_samples():
    wh = _WorkerHandle(0, None, None)
    assert wh.clock_offset is None
    wh.note_clock(time.perf_counter() - 0.010)   # slow handshake
    first = wh.clock_offset
    assert first == pytest.approx(0.010, abs=0.005)
    wh.note_clock(time.perf_counter() - 0.001)   # tighter sample wins
    assert wh.clock_offset < first
    wh.note_clock(time.perf_counter() - 0.020)   # looser sample ignored
    assert wh.clock_offset < first


def test_record_external_aligns_remote_clock(traced):
    # worker clock with a wildly different epoch (fresh process)
    skew = 123.456
    wh = _WorkerHandle(1, None, None)
    wh.note_clock(time.perf_counter() - skew)
    r0 = time.perf_counter() - skew          # remote span start = "now"
    busy = traced.record_external(
        [("run", r0, r0 + 0.002, {"note": "remote"})],
        offset=wh.clock_offset, pid=0, tid=obs.worker_tid(1),
        base_args={"wid": 1, "task": 5})
    assert busy == pytest.approx(0.002, abs=1e-9)
    ev = traced.events()[-1]
    assert ev.cat == "worker" and ev.tid == obs.worker_tid(1)
    # landed on the head timeline within the handshake latency
    assert abs(ev.t0 - time.perf_counter()) < 0.1
    assert ev.args == {"note": "remote", "wid": 1, "task": 5}


# ---------------------------------------------------------------------------
# Chrome-trace export + summarize
# ---------------------------------------------------------------------------

def _synthetic_round(rec):
    """A hand-built pfor round: head phases + one chunk on worker 1."""
    t = 100.0
    rec.name_node(0, "head-node")
    rec.name_track(0, obs.worker_tid(1), "worker1")
    rec.record("plan", "pfor", t, t + 0.01, args={"round": 0})
    rec.record("dispatch", "pfor", t + 0.01, t + 0.02, args={"round": 0})
    rec.record("run", "worker", t + 0.02, t + 0.08,
               tid=obs.worker_tid(1),
               args={"task": 1, "wid": 1, "round": 0, "lo": 0, "hi": 8,
                     "backend": "np"})
    rec.record("chunk_inflight", "pfor", t + 0.015, t + 0.085,
               tid=obs.worker_tid(1),
               args={"round": 0, "task": 1, "lo": 0, "hi": 8,
                     "backend": "np", "wid": 1, "ran": "np"})
    rec.record("gather", "pfor", t + 0.08, t + 0.095, args={"round": 0})
    rec.record("pfor_round", "pfor", t, t + 0.1,
               args={"round": 0, "name": "body", "unit": 0, "chunks": 1,
                     "workers": 1})
    rec.record("parse", "compile", t - 1.0, t - 0.99,
               args={"kernel": "k"})


def test_chrome_trace_schema_roundtrip(tmp_path, traced):
    _synthetic_round(traced)
    path = str(tmp_path / "trace.json")
    obs.export_chrome_trace(path, extra_meta={"suite": "test"})
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["suite"] == "test"
    assert doc["otherData"]["dropped"] == 0
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert {m["name"] for m in meta} >= {"process_name", "thread_name"}
    assert any(m["args"].get("name") == "worker1" for m in meta)
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0      # µs from min-t0 epoch
        assert {"pid", "tid", "cat", "name"} <= set(e)
    # timestamps re-based: earliest X event sits at the epoch
    assert min(e["ts"] for e in xs) == 0
    inflight = next(e for e in xs if e["name"] == "chunk_inflight")
    assert inflight["tid"] == obs.worker_tid(1)
    assert inflight["args"]["lo"] == 0


def test_summarize_reads_exported_trace(tmp_path, traced, capsys):
    _synthetic_round(traced)
    path = str(tmp_path / "trace.json")
    obs.export_chrome_trace(path)
    s = summarize.summarize(summarize.load_events(path))
    assert s["rounds_traced"] == 1
    assert s["workers"]["w1"]["run_spans"] == 1
    assert s["workers"]["w1"]["busy_s"] == pytest.approx(0.06, abs=1e-6)
    assert s["compile"]["k"]["parse"] == pytest.approx(0.01, abs=1e-6)
    [cp] = s["critical_paths"]
    assert cp["gating_chunk"]["wid"] == 1
    assert "% of round wall" in s["dominant"]["statement"]
    # the CLI contract the CI smoke relies on: exit 0, valid --json
    assert summarize.main([path, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["dominant"]["phase"] in ("plan", "dispatch", "gather",
                                        "split", "ship", "merge")


def test_summarize_rejects_garbage(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert summarize.main([str(bad)]) == 2
    assert summarize.main([str(tmp_path / "missing.json")]) == 2


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_scopes_and_snapshot():
    reg = MetricsRegistry()
    sc = reg.unique_scope("thing")
    sc2 = reg.unique_scope("thing")
    assert sc.prefix == "thing#0" and sc2.prefix == "thing#1"
    sc.inc("hits")
    sc.inc("hits", 2)
    sc.add_time("busy_s", 0.25)
    sc.dictmetric("routes")["np"] = 3
    assert sc.snapshot() == {"hits": 3, "busy_s": 0.25,
                             "routes": {"np": 3}}
    # prefix isolation: the sibling scope saw nothing
    assert sc2.snapshot() == {}
    # full-registry view keeps dotted names
    assert reg.snapshot()["thing#0.hits"] == 3


def test_registry_reset_keeps_live_references():
    reg = MetricsRegistry()
    sc = reg.scope("rt")
    c = sc.counter("n")
    d = sc.dictmetric("m")
    c.inc(5)
    d["k"] = 1
    reg.reset("rt")
    assert c.value == 0 and dict(d) == {}
    # the *same* objects are still registered — live holders keep working
    assert sc.counter("n") is c and sc.dictmetric("m") is d
    c.inc()
    assert reg.snapshot("rt")["n"] == 1


def test_registry_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.scope("a").counter("x")
    with pytest.raises(TypeError):
        reg.scope("a").gauge("x")


def test_phase_clock_tiles_a_call_and_counts_bytes(traced):
    scope = obs.metrics.unique_scope("clocktest")
    phases = obs.Phases(scope, "k", ("a", "b", "c"), counts_bytes=("b",))
    clock = phases.start("a")
    clock.next("b")
    clock.moved(100)
    clock.moved(28)
    clock.next("c")
    run_s = clock.stop(after="a")
    snap = scope.snapshot()
    assert [snap[f"phase.{p}_s"]["count"] for p in "abc"] == [1, 1, 1]
    assert snap["b_bytes"] == 128
    assert run_s == pytest.approx(snap["phase.b_s"]["total"]
                                  + snap["phase.c_s"]["total"], abs=1e-9)
    spans = [e for e in traced.events() if e.cat == "kernel"]
    assert [e.name for e in spans] == ["kernel.a", "kernel.b", "kernel.c"]
    assert spans[1].args == {"kernel": "k", "bytes": 128}
    assert spans[0].args == {"kernel": "k"}
    # one phase's end is the next one's start: the phases tile the call
    for x, y in zip(spans, spans[1:]):
        assert x.t1 == y.t0


def test_counter_threaded_increments():
    c = Counter()

    def bump():
        for _ in range(500):
            c.inc()

    threads = [threading.Thread(target=bump) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 2000


def test_serve_engine_counters_alias_registry():
    from repro.serve.engine import ServeEngine

    eng = ServeEngine.__new__(ServeEngine)   # no model build needed
    eng.ticks = 0
    eng.ticks += 3
    eng.prefills = 2
    prefix = eng._mscope.prefix
    assert prefix.startswith("serveengine")
    assert eng.ticks == 3
    assert obs.metrics.get(f"{prefix}.ticks").value == 3
    assert obs.metrics.get(f"{prefix}.prefills").value == 2


def _twice(x: "ndarray[f64,1]", out: "ndarray[f64,1]", n: int):
    for i in range(0, n):
        out[i] = x[i] * 2.0


def test_compiled_kernel_stats_backed_by_registry():
    ck = compile_kernel(_twice, distribute=False)
    x = np.arange(4.0)
    out = np.zeros(4)
    ck(x, out, 4)
    assert np.allclose(out, x * 2)
    st = ck.stats()
    called = next(name for name, row in st["variants"].items()
                  if row["calls"] == 1)
    assert st["variants"][called]["total_s"] > 0
    reg_view = obs.metrics.snapshot(ck._mscope.prefix)
    assert reg_view[f"variants.{called}.calls"] == 1
    # legacy attribute writes land in the registry too
    ck.spec_hits += 4
    assert obs.metrics.snapshot(ck._mscope.prefix)["spec_hits"] == 4
    assert ck.stats()["spec_hits"] == 4


# ---------------------------------------------------------------------------
# live cluster: spans + registry end to end
# ---------------------------------------------------------------------------

def _obs_stap(A: "ndarray[f64,2]", s: "ndarray[f64,1]",
              out: "ndarray[f64,1]", N: int, M: int, iters: int):
    for i in range(0, N):
        w = 0.1 * s[0:M]
        for it in range(0, iters):
            w = w + 0.1 * (s[0:M] - A[i, 0:M] * w[0:M])
        out[i] = np.dot(w[0:M], A[i, 0:M])


def test_live_cluster_trace_covers_every_chunk(tmp_path, traced):
    rng = np.random.default_rng(11)
    N, M, iters = 32, 16, 8
    A = rng.normal(size=(N, M)) * 0.1
    s = rng.normal(size=M)
    out_ref = np.zeros(N)
    _obs_stap(A, s, out_ref, N, M, iters)

    path = str(tmp_path / "cluster_trace.json")
    rt = ClusterRuntime(workers=2, trace=path)
    try:
        ck = compile_kernel(_obs_stap, runtime=rt)
        assert ck.sched.has_pfor
        ck.pfor_config.distribute_threshold = 0
        out = np.zeros(N)
        ck.call_variant("np", A, s, out, N, M, iters)
        assert np.allclose(out, out_ref, atol=1e-12)

        st = rt.stats()
        assert st["chunks_dispatched"] > 0
        # legacy stats keys alias the runtime's registry scope
        prefix = rt._mscope.prefix
        reg = obs.metrics.snapshot(prefix)
        assert reg["chunks_dispatched"] == st["chunks_dispatched"]
        assert reg["bytes_shipped"] == st["bytes_shipped"]
        assert rt.chunks_dispatched == st["chunks_dispatched"]

        evs = traced.events()
        inflight = [e for e in evs if e.name == "chunk_inflight"]
        runs = [e for e in evs if e.cat == "worker" and e.name == "run"]
        assert len(inflight) == st["chunks_dispatched"]
        # every dispatched chunk produced a worker-side run span, keyed
        # by the same (task, lo, hi)
        run_keys = {(e.args["task"], e.args["lo"], e.args["hi"])
                    for e in runs}
        for e in inflight:
            key = (e.args["task"], e.args["lo"], e.args["hi"])
            assert key in run_keys, f"chunk {key} has no worker span"
            assert e.args["wid"] in (0, 1)
            # aligned onto the head clock: worker span nests inside its
            # in-flight envelope (offset ≤ one handshake latency)
            rspan = next(r for r in runs
                         if (r.args["task"], r.args["lo"],
                             r.args["hi"]) == key)
            assert rspan.t0 >= e.t0 - 0.05
            assert rspan.t1 <= e.t1 + 0.05
        # round accounting made it into the phase counters
        ph = rt.phase_breakdown()
        assert ph["round_s"] > 0 and ph["compute_s"] > 0
        assert ph["gather_s"] > 0
        assert rt.telemetry()["phases"] == ph
    finally:
        rt.shutdown()

    # shutdown exported the Perfetto trace; the analyzer accepts it and
    # sees every worker compute
    assert summarize.main([path, "--json"]) == 0
    s_doc = summarize.summarize(summarize.load_events(path))
    assert s_doc["rounds_traced"] >= 1
    for w, row in s_doc["workers"].items():
        assert row["run_spans"] > 0, f"{w} has no compute spans"


# ---------------------------------------------------------------------------
# the phases of a compiled kernel's call (last in the file: these run
# jax, and the live cluster above forks its workers)
# ---------------------------------------------------------------------------

def _gemm(alpha: float, beta: float, C: "ndarray[f32,2]",
          A: "ndarray[f32,2]", B: "ndarray[f32,2]",
          NI: int, NJ: int, NK: int):
    C[0:NI, 0:NJ] = beta * C[0:NI, 0:NJ] + alpha * np.dot(
        A[0:NI, 0:NK], B[0:NK, 0:NJ])


def _gemm_case(seed=0, ni=24, nj=16, nk=32):
    """An optimize()d gemm that takes its whole-kernel jnp variant, and
    operands of a known small shape."""
    ck = optimize(_gemm)
    ck.accel_threshold = 0.0
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((ni, nk)).astype(np.float32)
    b = rng.standard_normal((nk, nj)).astype(np.float32)
    c = rng.standard_normal((ni, nj)).astype(np.float32)
    return ck, (1.5, 1.2, c, a, b, ni, nj, nk)


def _phase_counts(ck):
    snap = obs.metrics.snapshot(ck._mscope.prefix)
    return ({p: snap[f"phase.{p}_s"]["count"] for p in PHASES},
            snap["h2d_bytes"], snap["d2h_bytes"])


def test_kernel_call_records_each_phase_once_with_its_bytes():
    ck, args = _gemm_case()
    c0 = args[2].copy()
    ck(*args)
    assert ck.last_dispatch.variant == "jnp"
    counts, h2d, d2h = _phase_counts(ck)
    assert counts == {p: 1 for p in PHASES}
    assert h2d == sum(a.nbytes for a in args if isinstance(a, np.ndarray))
    assert d2h == args[2].nbytes
    # the variant's own time is the sum of the phases after dispatch
    snap = obs.metrics.snapshot(ck._mscope.prefix)
    assert ck.variants["jnp"].total_s == pytest.approx(
        sum(snap[f"phase.{p}_s"]["total"] for p in PHASES[1:]), abs=1e-9)
    # the same output as the variant called on the NumPy operands, as
    # the dispatcher called it before it owned the transfer in
    with backends.device_precision():
        (before,) = ck.variants["jnp"].fn(*args[:2], c0, *args[3:])
    assert np.array_equal(args[2], np.asarray(before))
    # a forced variant records its phases but no dispatch; the np
    # variant records only its body
    ck.call_variant("jnp", *args)
    ck.call_variant("np", *args)
    counts, h2d2, d2h2 = _phase_counts(ck)
    assert counts == {"dispatch": 1, "h2d": 2, "body": 3, "wait": 2,
                      "d2h": 2, "copy_back": 2}
    assert (h2d2, d2h2) == (2 * h2d, 2 * d2h)


def test_kernel_call_that_raises_records_no_phase():
    ck, args = _gemm_case()
    bad = list(args)
    bad[4] = bad[4][:-1]             # B one row short of NK: the body fails
    with pytest.raises(Exception):
        ck.call_variant("jnp", *bad)
    counts, h2d, d2h = _phase_counts(ck)
    assert counts == {p: 0 for p in PHASES}
    assert (h2d, d2h) == (0, 0)


def test_kernel_phases_leave_the_ring_empty_when_dark():
    assert not obs.enabled()
    obs.recorder().clear()
    ck, args = _gemm_case()
    ck(*args)
    ck(*args)
    assert len(obs.recorder()) == 0
    assert _phase_counts(ck)[0]["copy_back"] == 2


def test_kernel_phases_are_host_events_of_a_profiler_trace(tmp_path):
    import jax

    ck, args = _gemm_case()
    ck(*args)                        # compiles outside the trace
    with jax.profiler.trace(str(tmp_path)):
        ck(*args)
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                       recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    events = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for p in data.planes if p.name.startswith("/host:")
                    for ln in p.lines for e in ln.events
                    if e.name.startswith("kernel."))
    assert [name for _, _, name in events] == [f"kernel.{p}"
                                               for p in PHASES]
    for (_, end, _), (start, _, _) in zip(events, events[1:]):
        assert end <= start


def test_summarize_reports_each_kernel_phase(tmp_path, traced, capsys):
    ck, args = _gemm_case()
    ck(*args)
    ck(*args)
    spans = [e for e in traced.events() if e.cat == "kernel"]
    assert [e.name for e in spans[:6]] == [f"kernel.{p}" for p in PHASES]
    assert spans[1].args == {"kernel": "_gemm",
                             "bytes": _phase_counts(ck)[1] // 2}
    path = str(tmp_path / "trace.json")
    obs.export_chrome_trace(path)
    s = summarize.summarize(summarize.load_events(path))
    k = s["kernels"]["_gemm"]
    assert k["calls"] == 2
    assert set(k["phases"]) == set(PHASES)
    assert k["phases"]["h2d"]["count"] == 2
    assert k["phases"]["h2d"]["bytes"] == _phase_counts(ck)[1]
    assert k["phases"]["d2h"]["bytes"] == 2 * args[2].nbytes
    assert k["phases"]["body"]["mean_s"] == pytest.approx(
        k["phases"]["body"]["total_s"] / 2, abs=1e-9)
    assert summarize.main([path]) == 0
    out = capsys.readouterr().out
    assert "== kernel _gemm (2 call(s)) ==" in out
    assert "copy_back" in out
