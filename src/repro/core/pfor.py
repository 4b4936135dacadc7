"""pfor execution: the generated `__pfor_run` hook.

Generated kernels call ``__pfor_run(body, lo, hi, tile)`` where ``body(lo,
hi)`` executes a contiguous chunk of dependence-free iterations, writing
disjoint regions of the output arrays in place.

Two outcomes (a profitability decision, §4.3):
  * on the caller — one call of the body; chosen when no runtime is
    bound, sequential execution is forced, the range is trivial, or the
    runtime judges distribution unprofitable;
  * cluster shards — the bound runtime (:class:`repro.distrib.
    ClusterRuntime`) ships the body closure to worker processes, sizes
    chunks by measured device capability, and gathers the disjoint-
    region writes back on the head. The local-vs-distributed call is
    made per kernel from the fleet's device profiles
    (:func:`repro.core.cost.cluster_distribute_profitable`).

The SPMD (shard_map) mapping of regular pfor loops lives in the LM planner
(core/planner.py) — numeric kernels distribute via the cluster runtime,
matching the paper's Ray deployment.
"""

from __future__ import annotations

import math
from typing import Callable, Optional, Tuple

# the calls a bound runtime must provide (checked by compile_kernel)
RUNTIME_PROTOCOL = ("pfor_shards", "distribute_profitable")


def check_runtime(runtime) -> None:
    """Raise ``TypeError`` unless ``runtime`` is None or implements
    :data:`RUNTIME_PROTOCOL`."""
    if runtime is None:
        return
    missing = [m for m in RUNTIME_PROTOCOL
               if not callable(getattr(runtime, m, None))]
    if missing:
        raise TypeError(
            f"runtime {type(runtime).__name__} lacks "
            f"{', '.join(missing)}: a pfor runtime provides "
            f"{' and '.join(RUNTIME_PROTOCOL)}")


class PforConfig:
    """Mutable knob block bound into each compiled kernel.

    A bound ``runtime`` provides exactly two calls:

    * ``distribute_profitable(flops, bcast_bytes, n_chunks, *,
      sliced_bytes)`` — whether shipping the loop beats running it on
      the caller; ``bcast_bytes`` ride to every worker, ``sliced_bytes``
      ship once in total, split by row;
    * ``pfor_shards(body, lo, hi, tile, *, written, sliceable,
      est_flops)`` — run ``body`` over ``[lo, hi)`` in chunks and merge
      their writes to the ``written`` arrays into the caller's arrays;
      ``sliceable`` names the arrays that may ship as per-chunk row
      slices, ``est_flops`` is the kernel's FLOP estimate.

    ``distribute_threshold <= 0`` skips the profitability question and
    always distributes."""

    def __init__(self, runtime=None, tile: Optional[int] = None,
                 workers: int = 4, force_sequential: bool = False):
        self.runtime = runtime          # ClusterRuntime | None
        self.tile = tile
        self.workers = workers
        self.force_sequential = force_sequential
        # filled per call by the dispatcher (profitability input):
        self.estimated_flops = 0.0
        self.distribute_threshold = 1e7
        # arrays the schedule writes (set by the compiler) — lets the
        # cluster runtime diff only real outputs when gathering chunks
        self.written: Tuple[str, ...] = ()

    def make_runner(self) -> Callable:
        def __pfor_run(body, lo, hi, tile):
            n = max(0, hi - lo)
            if n == 0:
                return
            if self.force_sequential or self.runtime is None or n <= 1:
                body(lo, hi)
                return
            # codegen stamps every pfor body with the arrays its unit
            # proves chunk-sliceable (possibly empty)
            sliceable = tuple(getattr(body, "__sliceable__", ()))
            if self.distribute_threshold > 0:
                # the cluster's own payload sizing rule (imported here:
                # the caller-only path never loads repro.distrib)
                from repro.distrib.serial import payload_split_nbytes

                tile_ = tile or self.tile
                if tile_ is None:
                    tile_ = max(1, math.ceil(n / max(1, self.workers)))
                bcast, sliced = payload_split_nbytes(body, sliceable)
                if not self.runtime.distribute_profitable(
                        self.estimated_flops, bcast,
                        max(1, math.ceil(n / tile_)),
                        sliced_bytes=sliced):
                    body(lo, hi)
                    return
            # est_flops is the dispatcher's kernel-level estimate: the
            # sharder prices per-(unit, backend, worker) cells from it
            self.runtime.pfor_shards(
                body, lo, hi, tile or self.tile, written=self.written,
                sliceable=sliceable, est_flops=self.estimated_flops)

        return __pfor_run
