"""End-to-end behaviour tests for the paper's system."""

import numpy as np
import pytest


def _covariance_unhinted(data, cov, mean, M, N):
    for j in range(0, M):
        mean[j] = 0.0
        for i in range(0, N):
            mean[j] = mean[j] + data[i, j]
        mean[j] = mean[j] / N
    for i in range(0, N):
        for j in range(0, M):
            data[i, j] = data[i, j] - mean[j]
    for i in range(0, M):
        for j in range(i, M):
            cov[i, j] = 0.0
            for k in range(0, N):
                cov[i, j] = cov[i, j] + data[k, i] * data[k, j]
            cov[i, j] = cov[i, j] / (N - 1.0)
            cov[j, i] = cov[i, j]


def test_end_to_end_profile_compile_dispatch_restart(tmp_path):
    """The closed loop the profiler subsystem adds to the paper flow:
    trace an *unhinted* kernel → synthesize hints → compile → dispatch
    (allclose with the original), then warm-start a fresh compiler
    instance from the persistent cache (codegen skipped, verified by
    telemetry)."""
    from repro.core.compiler import compile_kernel, optimize
    from repro.profiler import VariantCache, synthesize_hints

    M, N = 14, 18
    rng = np.random.default_rng(2)
    data0 = rng.normal(size=(N, M))
    ref_cov = np.zeros((M, M))
    _covariance_unhinted(data0.copy(), ref_cov, np.zeros(M), M, N)

    profiled = optimize(_covariance_unhinted, profile=True, warmup=2)
    for _ in range(4):
        cov, mean = np.zeros((M, M)), np.zeros(M)
        profiled(data0.copy(), cov, mean, M, N)
        np.testing.assert_allclose(cov, ref_cov, atol=1e-8)
    assert profiled.compiled is not None
    assert profiled.compiled.last_dispatch.legality_ok

    cache_dir = str(tmp_path / "vcache")
    hints = synthesize_hints(profiled.trace)
    compile_kernel(_covariance_unhinted, hints=hints,
                   cache=VariantCache(cache_dir))
    warm = VariantCache(cache_dir)           # fresh instance: "restart"
    ck = compile_kernel(_covariance_unhinted, hints=hints, cache=warm)
    assert warm.stats.codegen_skipped == 1 and ck.from_cache
    cov, mean = np.zeros((M, M)), np.zeros(M)
    ck(data0.copy(), cov, mean, M, N)
    np.testing.assert_allclose(cov, ref_cov, atol=1e-8)


def test_end_to_end_correlation_paper_flow():
    """The paper's running example (Figs. 1/2/6): both input styles
    compile, raise the triangular loop to dot, dispatch through the
    multi-version tree, and agree with ground truth."""
    from benchmarks.polybench_kernels import (KERNELS, clone_args,
                                              to_lists)
    from repro.core.compiler import compile_kernel

    k = KERNELS["correlation"]
    rng = np.random.default_rng(123)
    args, meta = k["make_args"](32, rng)
    ref_args = clone_args(args)
    k["ref"](*ref_args)

    for style in ("np", "list"):
        ck = compile_kernel(k[style])
        t_args = clone_args(args)
        if style == "list":
            t_args = to_lists(t_args)
        ck(*t_args)  # full dispatcher path (legality → profitability)
        np.testing.assert_allclose(
            np.asarray(t_args[2], float), np.asarray(ref_args[2], float),
            atol=1e-7, err_msg=f"correlation {style} corr matrix")
        assert ck.last_dispatch.legality_ok


@pytest.mark.slow
def test_end_to_end_training_loss_decreases():
    """Tiny LM trained on learnable synthetic data: loss must drop."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.data.pipeline import DataConfig, SyntheticTokens
    from repro.train import make_init, make_train_step

    from repro.train import AdamWConfig

    cfg = get_smoke_config("stablelm_3b")
    cfg.microbatch = 1
    opt_cfg = AdamWConfig(lr=3e-3, weight_decay=0.0)
    init = make_init(cfg, opt_cfg)
    params, opt, _ = init(jax.random.key(0))
    step = jax.jit(make_train_step(cfg, opt_cfg))
    data = SyntheticTokens(DataConfig(vocab=cfg.vocab, seq_len=32,
                                      global_batch=8))
    losses = []
    for i in range(40):
        b = data.batch_at(i)
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses[::10]
    assert not any(np.isnan(x) for x in losses)


@pytest.mark.slow
def test_end_to_end_checkpoint_restart_resume():
    """Fault-tolerance drill: train, checkpoint, 'crash', restore, and
    verify identical continuation."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from repro.checkpoint import ckpt as C
    from repro.configs import get_smoke_config
    from repro.data.pipeline import DataConfig, SyntheticTokens
    from repro.train import make_init, make_train_step

    cfg = get_smoke_config("gemma2_2b")
    init = make_init(cfg)
    params, opt, _ = init(jax.random.key(1))
    step = jax.jit(make_train_step(cfg))
    data = SyntheticTokens(DataConfig(vocab=cfg.vocab, seq_len=16,
                                      global_batch=4))

    def run(params, opt, start, n):
        m = None
        for i in range(start, start + n):
            b = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
            params, opt, m = step(params, opt, b)
        return params, opt, m

    params, opt, _ = run(params, opt, 0, 3)
    with tempfile.TemporaryDirectory() as d:
        C.save(d, 3, {"params": params, "opt": opt})
        p_a, o_a, m_a = run(params, opt, 3, 2)
        like = {"params": jax.tree.map(jnp.zeros_like, params),
                "opt": jax.tree.map(jnp.zeros_like, opt)}
        got, _ = C.restore(d, 3, like)
        p_b, o_b, m_b = run(got["params"], got["opt"], 3, 2)
        assert float(m_a["loss"]) == pytest.approx(float(m_b["loss"]),
                                                   rel=1e-5)


def test_end_to_end_stap_with_fault_injection():
    """STAP pipeline distributed over the cluster runtime keeps producing
    correct results while a worker holding a chunk is SIGKILLed: the
    head resubmits the lost chunk on the survivors."""
    import threading
    import time

    from benchmarks.stap import FFT_SIZE, make_data, stap_kernel, stap_ref
    from repro.core.compiler import compile_kernel
    from repro.distrib import ClusterRuntime, chaos

    cubes, sv, mf, out = make_data(n_cubes=6)
    out_ref = out.copy()
    stap_ref(cubes, sv, mf, out_ref, 6, FFT_SIZE)

    with ClusterRuntime(workers=2) as rt:
        # every chunk on the original fleet stays in flight long enough
        # for the kill to land on one (a respawned worker runs at speed)
        for wid in list(rt._handles):
            assert chaos.slow(rt, wid, per_task_s=0.5) == wid
        ck = compile_kernel(stap_kernel, runtime=rt, tile=2)
        ck.pfor_config.distribute_threshold = 0
        killed = []

        def kill_a_busy_worker():
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                with rt._lock:
                    # dispatched_at is stamped once the chunk is sent
                    busy = [t.wid for t in rt._tasks.values()
                            if not t.finished
                            and t.dispatched_at is not None]
                if busy:
                    killed.append(rt.kill_worker(busy[0]))
                    return
                time.sleep(0.005)

        killer = threading.Thread(target=kill_a_busy_worker)
        killer.start()
        out_got = out.copy()
        ck.call_variant("np", cubes, sv, mf, out_got, 6, FFT_SIZE)
        killer.join()
        np.testing.assert_allclose(out_got, out_ref, atol=1e-9)
        assert killed and killed[0] is not None
        st = rt.stats()
        assert st["pfor_runs"] == 1
        assert st["chunks_dispatched"] >= 3
        assert st["worker_deaths"] == 1
        assert st["resubmits"] >= 1
