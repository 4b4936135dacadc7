"""Batched serving engine with continuous batching.

Requests are admitted into free cache slots, then all active slots
advance together through one jit'd batched decode step per tick — new
requests join between ticks without recompilation (static shapes).

Admission prefills a prompt in fixed-size chunks into a one-slot cache
(KV at the cache index; Mamba conv and recurrent state carried from
chunk to chunk), then inserts every kind of state into the request's
slot. One chunk shape and one decode shape serve every prompt length
and context. Greedy sampling happens inside the decode step; per-request
max_tokens / eos termination.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from repro.kernels.decode_attention import ops as decode_ops
from repro.models import transformer as T
from repro.models.common import ArchConfig

from .kvcache import SlotMap
from .request import Request

__all__ = ["Request", "ServeEngine", "LMSteps", "lm_steps", "prefill_into"]

PREFILL_CHUNK = 256
TICK_PHASES = ("admit", "issue", "wait", "d2h", "book")


@dataclass(frozen=True)
class LMSteps:
    """The jitted programs of a serving loop for one (config, max_seq):
    ``chunk(params, tokens (1, C), one_cache, valid (1,))`` → (one_cache,
    logits (1, V), stats); ``decode(params, tokens (B, 1), valid (B,),
    caches)`` → (next tokens (B,), logits (B, V), caches, stats);
    ``insert(caches, one_cache, slot)`` → caches."""
    cfg: ArchConfig
    max_seq: int
    chunk_len: int
    chunk: Callable
    decode: Callable
    insert: Callable


def _insert_impl(caches, one, slot):
    """Write a batch-1 cache into batched caches at `slot`."""
    def ins(big, small):
        return jax.lax.dynamic_update_slice_in_dim(
            big, small.astype(big.dtype), slot, axis=1)

    return jax.tree.map(ins, caches, one)


def lm_steps(cfg: ArchConfig, max_seq: int, *,
             prefill_chunk: int = PREFILL_CHUNK,
             donate: bool = True) -> LMSteps:
    """Build the serving loop's programs. The chunk length is the
    largest divisor of ``max_seq`` up to ``prefill_chunk``, so that no
    chunk's write runs past the cache. ``donate`` hands the caches'
    buffers to each program (in-place updates); a caller that keeps old
    cache states alive (lineage replay) passes False."""
    c = min(prefill_chunk, max_seq)
    while max_seq % c:
        c -= 1

    def _chunk(params, tokens, one, valid):
        return T.prefill_chunk(params, tokens, one, cfg, valid)

    def _decode(params, tokens, valid, caches):
        logits, caches, stats = T.decode_step(
            params, tokens, caches, cfg, valid=valid, with_stats=True)
        return (jnp.argmax(logits, axis=-1).astype(jnp.int32), logits,
                caches, stats)

    def jit(fn, caches_arg: int):
        return jax.jit(fn, donate_argnums=(caches_arg,) if donate else ())

    return LMSteps(cfg, max_seq, c, jit(_chunk, 2), jit(_decode, 3),
                   jit(_insert_impl, 0))


def prefill_into(steps: LMSteps, params, caches, prompt, slot: int, *,
                 phases=None):
    """Prefill ``prompt`` chunk by chunk into a fresh one-slot cache and
    insert it into ``caches`` at ``slot``. With ``phases`` (an
    :class:`repro.obs.Phases` of one phase, ``chunk``) each chunk is
    timed to its end. Returns (caches, first greedy token, last chunk's
    logits, stats of each chunk)."""
    prompt = np.asarray(prompt, np.int32)
    n = len(prompt)
    c = steps.chunk_len
    if not 0 < n < steps.max_seq:
        raise ValueError(f"prompt of {n} tokens does not fit a cache of "
                         f"{steps.max_seq}")
    one = T.init_caches(steps.cfg, 1, steps.max_seq)
    all_stats = []
    for off in range(0, n, c):
        piece = prompt[off:off + c]
        buf = np.zeros((1, c), np.int32)
        buf[0, :len(piece)] = piece
        valid = np.asarray([len(piece)], np.int32)
        clock = phases.start("chunk") if phases is not None else None
        one, logits, stats = steps.chunk(params, buf, one, valid)
        if clock is not None:
            jax.block_until_ready(logits)
            clock.stop()
        all_stats.append(stats)
    tok = int(jnp.argmax(logits[0]))
    caches = steps.insert(caches, one, jnp.int32(slot))
    return caches, tok, logits, all_stats


class ServeEngine:
    """Continuous-batching engine.

    ``kernel_registry`` (a :class:`repro.profiler.specializer.Specializer`
    or a plain ``{name: CompiledKernel}`` dict) and ``variant_cache``
    (:class:`repro.profiler.cache.VariantCache`) are optional attachments;
    when present, :meth:`telemetry` folds their dispatch/cache counters
    into the engine's serving stats so one endpoint answers "what is the
    compiler doing under this traffic".

    Serving counters are registry-backed (``serve#N`` scope of
    ``obs.metrics``) via MetricAttr descriptors — attribute semantics
    unchanged, values readable alongside cluster/kernel metrics. Each
    tick runs on one :class:`repro.obs.Phases` clock (histograms
    ``phase.<admit|issue|wait|d2h|book>_s``, profiler events
    ``tick.<phase>``), each prefill chunk on another
    (``phase.chunk_s``, ``prefill.chunk``). The decode step's MoE counts
    come back in the same transfer as its tokens and land in the
    counters ``moe.assign_held``, ``moe.assign_total``,
    ``moe.experts_hit`` (held experts that computed a token, summed over
    MoE layers and ticks) and ``moe.dropped``; ``cache.kv_bytes`` and
    ``cache.state_bytes`` sum, over ticks, the KV at the active slots'
    lengths and their recurrent state; ``cache.kv_read_bytes`` the KV
    the decode's attention read (``kv_read_positions`` of
    ``repro.kernels.decode_attention.ops``).

    ``last_logits`` holds the last decode step's logits (B, Vpad) on
    the device, for the caller."""

    ticks = obs.MetricAttr("ticks")
    prefills = obs.MetricAttr("prefills")
    prefill_chunks = obs.MetricAttr("prefill_chunks")
    tokens_generated = obs.MetricAttr("tokens_generated")

    def __init__(self, params, cfg: ArchConfig, *, n_slots: int = 4,
                 max_seq: int = 256, prefill_chunk: int = PREFILL_CHUNK,
                 kernel_registry=None, variant_cache=None):
        self.params = params
        self.cfg = cfg
        self.max_seq = max_seq
        self.slots = SlotMap(n_slots)
        self.caches = T.init_caches(cfg, n_slots, max_seq)
        self.queue: List[Request] = []
        self.active: Dict[int, Request] = {}
        self.finished: List[Request] = []
        self.kernel_registry = kernel_registry
        self.variant_cache = variant_cache
        self.last_logits = None
        self._tenant_of: Dict[str, str] = {}
        self._mscope = obs.metrics.unique_scope("serve")
        self.ticks = 0
        self.prefills = 0
        self.prefill_chunks = 0
        self.tokens_generated = 0
        self._tick = obs.Phases(self._mscope, self._mscope.prefix,
                                TICK_PHASES, prefix="tick")
        self._chunk = obs.Phases(self._mscope, self._mscope.prefix,
                                 ("chunk",), prefix="prefill")
        self._moe = {name: self._mscope.counter(f"moe.{name}")
                     for name in ("assign_held", "assign_total",
                                  "experts_hit", "dropped")}
        self._kv_bytes = self._mscope.counter("cache.kv_bytes")
        self._state_bytes = self._mscope.counter("cache.state_bytes")
        self._kv_read_bytes = self._mscope.counter("cache.kv_read_bytes")
        # bytes a slot holds: KV per position, recurrent state in all;
        # each attention layer's window and KV bytes per position
        self._kv_layers = [
            (jax.ShapeDtypeStruct(c["kv"]["k"].shape, c["kv"]["k"].dtype),
             cfg.layer_window(i),
             (c["kv"]["k"].nbytes + c["kv"]["v"].nbytes)
             // (n_slots * max_seq))
            for i, c in enumerate(self.caches) if "kv" in c]
        self._kv_per_token = sum(per_pos for _, _, per_pos in self._kv_layers)
        self._state_per_slot = sum(
            leaf.nbytes for c in self.caches if "ssm" in c
            for leaf in jax.tree.leaves(c["ssm"])) // n_slots
        self.steps = lm_steps(cfg, max_seq, prefill_chunk=prefill_chunk)

    # latency histograms + per-tenant counters resolve through the
    # instance scope lazily (same contract as MetricAttr: an instance
    # built via __new__ in tests still gets a working telemetry surface)
    @property
    def _h_ttft(self):
        return obs.MetricAttr._scope_of(self).histogram("ttft_ms")

    @property
    def _h_tpot(self):
        return obs.MetricAttr._scope_of(self).histogram("tpot_ms")

    @property
    def _h_e2e(self):
        return obs.MetricAttr._scope_of(self).histogram("e2e_ms")

    @property
    def _t_requests(self):
        return obs.MetricAttr._scope_of(self).dictmetric("tenant_requests")

    @property
    def _t_tokens(self):
        return obs.MetricAttr._scope_of(self).dictmetric("tenant_tokens")

    # -- API ----------------------------------------------------------------
    def add_request(self, req: Request, tenant: str = "default") -> None:
        self._tenant_of[req.request_id] = tenant
        self._t_requests[tenant] = self._t_requests.get(tenant, 0) + 1
        self.queue.append(req)

    def _admit(self) -> None:
        while self.queue:
            slot = self.slots.allocate(self.queue[0].request_id)
            if slot is None:
                return
            req = self.queue.pop(0)
            req.slot = slot
            self.caches, tok, _, stats = prefill_into(
                self.steps, self.params, self.caches, req.prompt, slot,
                phases=self._chunk)
            self.prefill_chunks += len(stats)
            for st in stats:
                self._book_moe(jax.device_get(st), hits=False)
            req.generated.append(tok)
            self.prefills += 1
            self._count_token(req)
            req.first_token_s = time.perf_counter()
            self.slots.lengths[slot] = len(req.prompt) + 1
            self.active[slot] = req

    def _book_moe(self, stats, *, hits: bool) -> None:
        if not stats:
            return
        for name in ("assign_held", "assign_total", "dropped"):
            self._moe[name].inc(int(stats[name]))
        if hits:
            self._moe["experts_hit"].inc(
                int(np.count_nonzero(stats["expert_counts"])))

    def step(self) -> int:
        """One engine tick: admit + one batched decode. Returns number of
        active requests after the tick."""
        clock = self._tick.start("admit")
        self._admit()
        self.ticks += 1
        if not self.active:
            clock.stop()
            return 0
        clock.next("issue")
        n_slots = self.slots.n_slots
        tokens = np.zeros((n_slots, 1), np.int32)
        valid = np.zeros((n_slots,), np.int32)
        for slot, req in self.active.items():
            tokens[slot, 0] = req.generated[-1]
            valid[slot] = 1
        nxt, self.last_logits, self.caches, stats = self.steps.decode(
            self.params, tokens, valid, self.caches)
        clock.next("wait")
        jax.block_until_ready((nxt, stats))
        clock.next("d2h")
        next_tokens, stats = jax.device_get((nxt, stats))
        clock.next("book")
        self._book_moe(stats, hits=True)
        lengths = [int(self.slots.lengths[s]) if s in self.active else 0
                   for s in range(n_slots)]
        self._kv_bytes.inc(sum(lengths) * self._kv_per_token)
        self._kv_read_bytes.inc(sum(
            per_pos * decode_ops.kv_read_positions(lengths, k, window)
            for k, window, per_pos in self._kv_layers))
        self._state_bytes.inc(len(self.active) * self._state_per_slot)
        done_slots = []
        for slot, req in self.active.items():
            tok = int(next_tokens[slot])
            req.generated.append(tok)
            self._count_token(req)
            self.slots.advance(slot)
            if (len(req.generated) >= req.max_tokens
                    or (req.eos_id is not None and tok == req.eos_id)
                    or self.slots.lengths[slot] >= self.max_seq - 1):
                req.finished_s = time.perf_counter()
                done_slots.append(slot)
        for slot in done_slots:
            req = self.active.pop(slot)
            self._observe_finish(req)
            self.finished.append(req)
            self.slots.free(slot)
        clock.stop()
        return len(self.active)

    def _count_token(self, req: Request) -> None:
        self.tokens_generated += 1
        tenant = self._tenant_of.get(req.request_id, "default")
        self._t_tokens[tenant] = self._t_tokens.get(tenant, 0) + 1

    def _observe_finish(self, req: Request) -> None:
        """Land the request's latency stamps in the ``serve#N``
        histograms (TTFT / per-output-token / end-to-end)."""
        n_gen = max(1, len(req.generated) - 1)
        self._h_ttft.observe((req.first_token_s - req.submitted_s) * 1e3)
        self._h_e2e.observe((req.finished_s - req.submitted_s) * 1e3)
        self._h_tpot.observe(
            (req.finished_s - req.first_token_s) * 1e3 / n_gen)

    def run_until_done(self, max_ticks: int = 10_000) -> List[Request]:
        for _ in range(max_ticks):
            if not self.queue and not self.active:
                break
            self.step()
        return self.finished

    # -- telemetry ----------------------------------------------------------
    def telemetry(self) -> Dict[str, object]:
        """Serving + compiler-dispatch + variant-cache counters."""
        out: Dict[str, object] = {
            "ticks": self.ticks,
            "prefills": self.prefills,
            "prefill_chunks": self.prefill_chunks,
            "tokens_generated": self.tokens_generated,
            "queued": len(self.queue),
            "active": len(self.active),
            "finished": len(self.finished),
            "slot_utilization": self.slots.utilization(),
            "latency": {
                name: {"count": h.count, "mean": round(h.mean, 6),
                       "p50": h.percentile(50), "p95": h.percentile(95),
                       "p99": h.percentile(99)}
                for name, h in (("ttft_ms", self._h_ttft),
                                ("tpot_ms", self._h_tpot),
                                ("e2e_ms", self._h_e2e))},
            "tenants": {"requests": dict(self._t_requests),
                        "tokens": dict(self._t_tokens)},
        }
        reg = self.kernel_registry
        if reg is not None:
            if hasattr(reg, "telemetry"):        # Specializer
                out["kernels"] = reg.telemetry()
            else:                                # plain dict of kernels
                out["kernels"] = {
                    name: ck.stats() for name, ck in reg.items()
                    if hasattr(ck, "stats")}
        if self.variant_cache is not None:
            out["variant_cache"] = self.variant_cache.telemetry()
        return out
