"""Worker-side task functions for cluster LM serving.

The cluster ships task functions with :func:`repro.distrib.serial.dumps_fn`,
which pickles a function's non-module globals **by value**. A bare
module-level dict referenced from a shipped function would therefore
arrive as a private copy per task — a jit cache that never hits. The
rule this module is built around: shipped entry points
(``lm_boot``/``lm_prefill``/``lm_decode``/``lm_out``/``lm_anchor``)
reference only module-level *functions* (pickle serializes those by
reference, so the worker imports this module and resolves the real
objects). All mutable state — the per-config jit cache ``_JITS`` —
lives behind those by-reference functions and persists across tasks
inside each worker process.

State travels as a :class:`Resident`: a wrapper whose ``nbytes``
reports at least 64 KiB so the worker's result-residency rule
(``repro.distrib.worker.INLINE_MAX``) keeps the params+KV state in the
worker's object store instead of inlining it back to the head every
tick. Only ``lm_out``'s token vector — a few bytes — rides the wire
per decode step. Pickling (lineage anchors, head fetches for
re-anchoring) converts jax leaves to numpy so a Resident crosses
processes without a live jax runtime on the sending side's devices.

The decode math is the single-process engine's own: the programs of
:func:`repro.serve.engine.lm_steps` (chunked prefill into a one-slot
cache → insert → batched decode with greedy sampling inside the step)
driven by :func:`repro.serve.engine.prefill_into`, which is what makes
the cluster engine's token streams **bitwise-identical** to
:class:`repro.serve.engine.ServeEngine`. The worker's programs do not
donate their caches: lineage may still hold an older state.
"""

from __future__ import annotations

import numpy as np

__all__ = ["Resident", "lm_boot", "lm_prefill", "lm_decode", "lm_out",
           "lm_anchor", "tree_np"]

# worker residency floor: anything reporting more bytes than
# repro.distrib.worker.INLINE_MAX stays in the worker object store
_RESIDENT_FLOOR = 1 << 16

_JITS: dict = {}   # (cfg.name, dtype, max_seq) → engine.LMSteps


def tree_np(tree):
    """Recursively convert array leaves (jax or numpy) to numpy."""
    if isinstance(tree, dict):
        return {k: tree_np(v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(tree_np(v) for v in tree)
    if hasattr(tree, "shape") and hasattr(tree, "dtype"):
        return np.asarray(tree)
    return tree


def _tree_nbytes(tree) -> int:
    if isinstance(tree, dict):
        return sum(_tree_nbytes(v) for v in tree.values())
    if isinstance(tree, (list, tuple)):
        return sum(_tree_nbytes(v) for v in tree)
    return int(getattr(tree, "nbytes", 0) or 0)


class Resident:
    """Worker-resident serving state (+ the small per-step output that
    :func:`lm_out` extracts for the head)."""

    def __init__(self, value, out=None):
        self.value = value
        self.out = out
        self.nbytes = max(_tree_nbytes(value), _RESIDENT_FLOOR)

    def __getstate__(self):
        return {"value": tree_np(self.value), "out": tree_np(self.out),
                "nbytes": self.nbytes}

    def __setstate__(self, state):
        self.__dict__.update(state)


def _jits_for(cfg, max_seq: int):
    """Per-(config, max_seq) serving programs, cached for the life of
    the worker process — call 2 of a serving loop hits a compiled
    executable."""
    key = (cfg.name, str(getattr(cfg, "dtype", "")), int(max_seq))
    entry = _JITS.get(key)
    if entry is None:
        from repro.serve.engine import lm_steps

        entry = lm_steps(cfg, max_seq, donate=False)
        _JITS[key] = entry
    return entry


def _boot_impl(params, cfg, n_slots: int, max_seq: int) -> Resident:
    from repro.models import transformer as T
    caches = T.init_caches(cfg, n_slots, max_seq)
    state = {"params": params, "caches": caches, "cfg": cfg,
             "n_slots": int(n_slots), "max_seq": int(max_seq)}
    return Resident(state, out=np.zeros(0, np.int32))


def _prefill_impl(res: Resident, prompt, slot: int) -> Resident:
    from repro.serve.engine import prefill_into
    st = res.value
    steps = _jits_for(st["cfg"], st["max_seq"])
    caches, tok, _, _ = prefill_into(steps, st["params"], st["caches"],
                                     prompt, slot)
    new = dict(st)
    new["caches"] = caches
    return Resident(new, out=np.asarray([tok], np.int32))


def _decode_impl(res: Resident, tokens, valid) -> Resident:
    st = res.value
    steps = _jits_for(st["cfg"], st["max_seq"])
    nxt, _, caches, _ = steps.decode(st["params"], np.asarray(tokens),
                                     np.asarray(valid), st["caches"])
    new = dict(st)
    new["caches"] = caches
    return Resident(new, out=np.asarray(nxt, np.int32))


# -- shipped entry points (reference only module-level functions) -----------

def lm_boot(params, cfg, n_slots, max_seq):
    """Materialize fresh serving state (params + empty KV caches)."""
    return _boot_impl(params, cfg, n_slots, max_seq)


def lm_prefill(state, prompt, slot):
    """Prefill one prompt into ``slot``; out = its first greedy token."""
    return _prefill_impl(state, prompt, slot)


def lm_decode(state, tokens, valid):
    """One batched decode tick of the slots with ``valid`` 1; out =
    next token per slot."""
    return _decode_impl(state, tokens, valid)


def lm_out(state):
    """Extract the small per-step output (inlined back to the head)."""
    return np.asarray(state.out)


def lm_anchor(state):
    """Re-root lineage: the head attaches the full state value to this
    task's spec, so replay after a worker loss restarts here instead of
    walking the whole decode history."""
    return state
