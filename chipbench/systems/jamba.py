"""The Jamba2-Mini configurations: a one-chip share of an
expert-parallel deployment served by the program's ``ServeEngine``, in
the benchmark's own process, which holds the chip.

Set-up draws the weights from the seed (``reference/jamba.py``'s draws,
handed to the program in its parameter layout), builds the engine and
queues one session a slot. The warm call is the engine's first tick: it
admits every session (chunked prefill into its slot) and decodes once.
Each timed call is one ``ServeEngine.step()``: one greedy token for
every slot, back on the host. The check runs the plain reference over
two slots' whole sequences (prompt and generated tokens) and compares
the logits of the kept calls.
"""

from __future__ import annotations

import gc
from typing import Dict, List

import numpy as np

from chipbench import devtrace
from chipbench.harness import annotation, say
from chipbench.reference import jamba as ref
from chipbench.systems.polybench import _Compiles

COUNTERS = ("moe.assign_held", "moe.assign_total", "moe.experts_hit",
            "moe.dropped", "cache.kv_bytes", "cache.state_bytes")


def arch_config(cfg: dict):
    """The program's configuration of this cut: ``jamba2_mini``'s, with
    the numbers of the benchmark's file (the same at full size)."""
    import dataclasses

    import jax.numpy as jnp
    from repro.configs import jamba2_mini

    dm = ref.dims(cfg)
    arch = jamba2_mini.ep_config(experts_held=dm.held, vocab=dm.vocab,
                                 layers=dm.layers)
    return dataclasses.replace(
        arch, d_model=dm.d, n_heads=dm.heads, kv_heads=dm.kv,
        head_dim=dm.hd, d_ff=dm.f, expert_d_ff=dm.f, n_experts=dm.router,
        experts_topk=dm.topk, ssm_state=dm.n, ssm_expand=dm.inner // dm.d,
        ssm_conv=dm.k, ssm_dt_rank=dm.r, norm_eps=dm.eps,
        period=dm.period, attn_idx=dm.attn_offset,
        moe_every=dm.moe_period, moe_offset=dm.moe_offset,
        dtype=jnp.dtype(cfg["dtype"]))


def program_params(seed: int, cfg: dict) -> dict:
    """The seeded weights in the program's parameter tree."""
    dm = ref.dims(cfg)
    blocks = []
    for i in range(dm.period):
        layer: Dict[str, dict] = {}
        for name in ref.layer_specs(dm, i):
            part, leaf = name.split(".")
            layer.setdefault(part, {})[leaf] = ref.draw(seed, dm, i, name)
        blocks.append(layer)
    return {"embed": ref.draw(seed, dm, -1, "embed"),
            "unembed": ref.draw(seed, dm, -1, "unembed"),
            "final_ln": ref.draw(seed, dm, -1, "final_ln"),
            "blocks": blocks}


class System:
    kernel = "jamba_decode"
    keep = None          # every call is kept for the check: about one
                         # position in ten is decided (PERF.md section 4)

    def __init__(self, cfg: dict, mix, seed: int, *, trace: bool,
                 require_chip: bool = True):
        self.cfg, self.mix, self.trace = cfg, mix, bool(trace)
        self.seed = int(seed)
        self.require_chip = require_chip
        self.dm = ref.dims(cfg)
        self.serving = cfg["serving"]
        self.engine = None
        self.requests: List = []
        self._log_dir = None
        self._ticks = self._slot_ticks = self._context = 0
        self._control: Dict[int, np.ndarray] = {}

    # -- set-up -----------------------------------------------------------
    def open(self) -> dict:
        import jax

        if self.require_chip:
            from repro.core.jaxcache import enable_compile_cache

            enable_compile_cache()
        _Compiles.install()
        devs = jax.devices()
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs)}

    def draw(self) -> None:
        """The sessions: prompt lengths log-uniform over [prompt_min,
        prompt_max], one in each of ``slots`` equal parts of the log
        range (so the total, and the prefill's work, is nearly the same
        for every seed), in an order drawn from the seed; ids uniform
        over the vocabulary slice; the two slots the check compares;
        and, for the control, a continuation of each compared prompt."""
        sv = self.serving
        n = sv["slots"]
        rng = np.random.default_rng([self.seed % (1 << 64), 0x7A3BA])
        lo, hi = np.log(sv["prompt_min"]), np.log(sv["prompt_max"])
        u = (rng.permutation(n) + rng.uniform(size=n)) / n
        lengths = np.exp(lo + (hi - lo) * u).astype(int)
        self.prompts = [rng.integers(0, self.dm.vocab, length,
                                     dtype=np.int32) for length in lengths]
        self.compare = sorted(int(s) for s in rng.choice(
            sv["slots"], sv["compare_slots"], replace=False))
        self.tails = {s: rng.integers(0, self.dm.vocab, 64, dtype=np.int32)
                      for s in self.compare}

    def prepare(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.serve.engine import Request, ServeEngine

        self.draw()
        sv = self.serving
        arch = arch_config(self.cfg)
        params = program_params(self.seed, self.cfg)
        say(f"memory after the weights: {self._memory()}")
        self.engine = ServeEngine(
            params, arch,
            n_slots=sv["slots"], max_seq=sv["max_seq"],
            prefill_chunk=sv["prefill_chunk"])
        self.requests = [Request(f"session-{i}", p,
                                 max_tokens=sv["max_tokens"])
                         for i, p in enumerate(self.prompts)]
        for req in self.requests:
            self.engine.add_request(req)
        # the compared slots' rows of the logits, compiled here and not
        # in the window
        rows = jnp.asarray(self.compare)
        self._pick = jax.jit(lambda logits: logits[rows, :self.dm.vocab])
        self._pick(jnp.zeros((sv["slots"], self.dm.vocab), arch.dtype))

    # -- calls ------------------------------------------------------------
    def refresh(self, step) -> None:
        """Untimed: count the context the next tick attends over."""
        eng = self.engine
        if eng.active:
            self._ticks += 1
            self._slot_ticks += len(eng.active)
            self._context += int(sum(eng.slots.lengths[s]
                                     for s in eng.active))

    def call(self, step) -> None:
        self.engine.step()

    def output(self, step) -> dict:
        """The compared slots' positions and logits at this call."""
        import jax

        reqs = [self.requests[s] for s in self.compare]
        pos = [len(r.prompt) + len(r.generated) - 2 for r in reqs]
        logits = jax.device_get(self._pick(self.engine.last_logits))
        return {"pos": pos, "logits": np.asarray(logits, np.float32)}

    def annotate(self, name: str):
        return annotation(self.trace, name)

    # -- readings ---------------------------------------------------------
    def snapshot(self) -> dict:
        eng = self.engine
        scope = eng._mscope
        out = {"compiles": _Compiles.count, "ticks": eng.ticks,
               "prefills": eng.prefills,
               "prefill_chunks": eng.prefill_chunks,
               "finished": len(eng.finished),
               "work.ticks": self._ticks,
               "work.slot_ticks": self._slot_ticks,
               "work.context": self._context}
        for name in COUNTERS:
            out[name] = scope.counter(name).value
        return out

    def path_problem(self, counters: dict, calls: int):
        bad = {k: counters.get(k, 0) for k in
               ("prefills", "prefill_chunks", "finished", "moe.dropped")}
        bad = {k: v for k, v in bad.items() if v}
        return f"in the window: {bad}" if bad else None

    def compile_stages(self) -> dict:
        return {}

    def device_work(self, rec):
        c = rec.counters
        return ref.decode_work(self.dm, c["work.ticks"],
                               c["work.slot_ticks"], c["work.context"],
                               c["moe.experts_hit"], c["moe.assign_held"])

    def start_trace(self, log_dir: str) -> None:
        import jax

        self._log_dir = log_dir
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(log_dir, profiler_options=opts)

    def stop_trace(self) -> List[str]:
        import jax

        jax.profiler.stop_trace()
        return devtrace.xplane_files(self._log_dir)

    @staticmethod
    def _memory() -> dict:
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        return {k: stats[k] for k in ("bytes_in_use", "bytes_limit")
                if k in stats}

    def memory_peak_bytes(self) -> int:
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))

    def close(self) -> None:
        """Free the program's state; the requests (tokens) stay."""
        self.engine = None
        gc.collect()

    # -- the check ----------------------------------------------------------
    def _sequences(self) -> Dict[int, np.ndarray]:
        """Each compared slot's tokens (prompt, then the generated ones
        or the control's continuation), padded with id 0 to ``max_seq``:
        the reference is causal, so the padding changes no position
        before it, and one shape for every seed and slot compiles once
        per checkout."""
        if self.requests:
            seqs = {s: np.concatenate([self.requests[s].prompt,
                                       np.asarray(self.requests[s].generated,
                                                  np.int32)])
                    for s in self.compare}
        else:
            seqs = {s: np.concatenate([self.prompts[s], self.tails[s]])
                    for s in self.compare}
        size = self.serving["max_seq"]
        return {s: np.pad(t, (0, size - len(t))) for s, t in seqs.items()}

    def check(self, kept: List[tuple]) -> dict:
        """The worst normwise relative error of a kept call's logits,
        over the compared slots, against the reference's forward over
        the slot's whole sequence, at the positions whose routing the
        configuration's precision decides. Where, in some MoE layer,
        the reference's router logits of the last expert chosen and the
        first left out lie closer than ``routing_margin``, bfloat16
        activations may choose the other expert; the logits then differ
        by that expert's whole part, at that position and, through the
        conv window, the ``d_conv - 1`` after it. Those positions are
        left out."""
        floor = self.cfg["routing_margin"]
        window = self.dm.k - 1
        worst, counted = float("inf"), 0
        seqs = self._sequences()
        for j, s in enumerate(self.compare):
            pos = sorted({out["pos"][j] for _, out in kept})
            if not pos:
                continue
            want, margin = ref.forward(self.seed, self.cfg, seqs[s], pos)
            row = {p: k for k, p in enumerate(pos)}
            for _, out in kept:
                p = out["pos"][j]
                err = ref.logits_err(out["logits"][j], want[row[p]])
                low = float(margin[max(0, p - window):p + 1].min())
                if low >= floor:
                    counted += 1
                    say(f"slot {s} position {p}: {err!r} (routing margin "
                        f"{low!r})")
                    worst = err if worst == float("inf") \
                        else max(worst, err)
        say(f"{counted} of {len(kept) * len(self.compare)} positions "
            f"decided")
        return {"logits_err": {"value": worst,
                               "limit": self.cfg["limits"]["logits_err"]}}

    def control_output(self, step) -> dict:
        """The control's logits for call ``step.call`` of a run with no
        program: the compared slots, teacher-forced on prompt and drawn
        continuation, at the call's position past the prompt."""
        seqs = self._sequences()
        if not self._control:
            for s in self.compare:
                pos = list(range(len(self.prompts[s]) - 1,
                                 len(self.prompts[s]) + len(self.tails[s])))
                self._control[s] = ref.forward(self.seed, self.cfg, seqs[s],
                                               pos, fp8=True)[0]
        k = step.call
        return {"pos": [len(self.prompts[s]) - 1 + k for s in self.compare],
                "logits": np.stack([self._control[s][k]
                                    for s in self.compare])}
