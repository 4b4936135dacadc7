"""Chunk-sliced argument shipping: sliceability analysis, the ChunkSlice
re-basing wrapper, split closure serialization, and sliced-vs-broadcast
execution equivalence (deterministic grid always; hypothesis widens the
same property to random shapes/patterns when installed).

The load-bearing property (ISSUE 4): for affine pfor bodies, sliced
execution is **bitwise** equal to full-broadcast execution, and the
analysis never marks an array sliceable whose accesses step outside its
chunk rows.
"""

import linecache
import pickle
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core import cost
from repro.core.compiler import compile_kernel
from repro.core.schedule import PforUnit, _flatten
from repro.distrib import DeviceProfile
from repro.distrib.cluster import ClusterRuntime, ClusterTaskError
from repro.distrib.serial import (ChunkSlice, assemble_fn,
                                  payload_split_nbytes, rebase_chunk,
                                  split_fn)
from repro.distrib.worker import _chunk_updates

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def pfor_units(ck):
    return [u for u in _flatten(ck.sched.units) if isinstance(u, PforUnit)]


def run_sliced_inprocess(body, lo, hi, written, sliceable, n_chunks=3):
    """The cluster's slicing path without processes: split the closure,
    assemble each chunk with re-based row slices, diff its writes, and
    merge them back through the head's gather — the exact worker/head
    code, minus the pipe."""
    from repro.distrib.serial import closure_arrays

    arrays = {n: v for n, v in closure_arrays(body).items()
              if isinstance(v, np.ndarray)}
    slice_names = tuple(nm for nm in sliceable
                        if nm in arrays and arrays[nm].ndim >= 1
                        and lo >= 0 and arrays[nm].shape[0] >= hi)
    parts = split_fn(body, slice_names)
    bcast = {n: pickle.loads(b) for n, b in parts.cell_pkls.items()}
    edges = np.linspace(lo, hi, n_chunks + 1).astype(int)
    for c in range(n_chunks):
        clo, chi = int(edges[c]), int(edges[c + 1])
        if chi <= clo:
            continue
        fn, cellmap = assemble_fn(parts.skeleton, bcast)
        for nm in slice_names:
            chunk = parts.sliced[nm][clo:chi].copy()
            cellmap[nm].cell_contents = rebase_chunk(chunk, clo)
        updates = _chunk_updates(fn, clo, chi, tuple(written))
        spec = SimpleNamespace(lo=clo, hi=chi, sliced=slice_names)
        ClusterRuntime._merge_updates(arrays, updates, spec)


class InProcessShards:
    """In-process pfor runtime: PforConfig dispatches pfor units here, so
    a compiled kernel exercises codegen's ``__sliceable__`` hand-off and
    the full slicing path synchronously in this process."""

    def __init__(self):
        self.calls = []

    def pfor_shards(self, body, lo, hi, tile, *, written, sliceable,
                    est_flops):
        self.calls.append(tuple(sliceable))
        run_sliced_inprocess(body, lo, hi, written, sliceable)

    def distribute_profitable(self, flops, payload_bytes, n_chunks, *,
                              sliced_bytes):
        return True


# ---------------------------------------------------------------------------
# analysis: what the schedule proves sliceable
# ---------------------------------------------------------------------------

def _recur_kernel_src(vec, dot, scal, oidx):
    """A pfor-forcing template: the inner Richardson-style recurrence on
    ``w`` cannot absorb, so the i-loop schedules as a PforUnit."""
    return (
        'import numpy as np\n'
        'def kern(A: "ndarray[f64,2]", B: "ndarray[f64,1]", '
        'C: "ndarray[f64,1]", out: "ndarray[f64,1]", '
        'N: int, M: int, T: int):\n'
        '    for i in range(0, N):\n'
        '        w = 0.5 * B[0:M]\n'
        '        for t in range(0, T):\n'
        f'            w = w + 0.25 * ({vec} - w[0:M])\n'
        f'        out[{oidx}] = np.dot(w[0:M], {dot}) + {scal}\n')


VEC_PATTERNS = ["A[i, 0:M]", "B[0:M]", "A[0:M, i]"]
DOT_PATTERNS = ["A[i, 0:M]", "B[0:M]"]
SCAL_PATTERNS = ["C[i]", "C[0]", "C[N - 1 - i]", "0.0"]
OIDX_PATTERNS = ["i", "N - 1 - i"]


def expected_sliceable(vec, dot, scal, oidx):
    """Ground-truth classification for the template's access patterns:
    an array is sliceable iff *every* access is row-``i`` on axis 0."""
    exp = set()
    a_accesses = [p for p in (vec, dot) if p.startswith("A")]
    if a_accesses and all(p == "A[i, 0:M]" for p in a_accesses):
        exp.add("A")
    if scal == "C[i]":
        exp.add("C")
    if oidx == "i":
        exp.add("out")
    return exp


_COMPILED = {}


def compile_template(vec, dot, scal, oidx, runtime=None):
    key = (vec, dot, scal, oidx, id(runtime))
    if key not in _COMPILED:
        src = _recur_kernel_src(vec, dot, scal, oidx)
        # register the exec'd source so inspect.getsource (the parser's
        # front door) can find it
        fname = f"<slicing-template-{abs(hash(key))}>"
        linecache.cache[fname] = (len(src), None,
                                  src.splitlines(True), fname)
        ns = {}
        exec(compile(src, fname, "exec"), ns)
        _COMPILED[key] = compile_kernel(ns["kern"], runtime=runtime,
                                        enable_jax=False)
    return _COMPILED[key]


# a hand-picked slice of the grid covering every pattern at least once
GRID = [
    ("A[i, 0:M]", "A[i, 0:M]", "C[i]", "i"),
    ("A[i, 0:M]", "B[0:M]", "C[0]", "i"),
    ("B[0:M]", "A[i, 0:M]", "C[N - 1 - i]", "i"),
    ("A[0:M, i]", "B[0:M]", "C[i]", "i"),
    ("A[0:M, i]", "A[i, 0:M]", "0.0", "i"),
    ("A[i, 0:M]", "A[i, 0:M]", "C[i]", "N - 1 - i"),
    ("B[0:M]", "B[0:M]", "0.0", "N - 1 - i"),
]


@pytest.mark.parametrize("vec,dot,scal,oidx", GRID)
def test_analysis_matches_expected(vec, dot, scal, oidx):
    ck = compile_template(vec, dot, scal, oidx)
    units = pfor_units(ck)
    assert units, "template must schedule a pfor unit"
    got = set(units[0].sliceable)
    assert got == expected_sliceable(vec, dot, scal, oidx), \
        (vec, dot, scal, oidx)
    # B is read whole every iteration: never sliceable
    assert "B" not in got


def test_stap_flagship_analysis():
    import sys
    sys.path.insert(0, ".")
    from examples.stap import stap_adaptive

    ck = compile_kernel(stap_adaptive, enable_jax=False)
    (u,) = pfor_units(ck)
    assert set(u.sliceable) == {"train", "snap", "outY"}
    # the generated body carries the hand-off attribute codegen emits
    assert "__sliceable__ = " in ck.source("np")


def test_offset_leading_index_not_sliceable():
    """A[i+1] reads one row past the chunk: must broadcast."""
    src = (
        'import numpy as np\n'
        'def kern(A: "ndarray[f64,2]", out: "ndarray[f64,1]", '
        'N: int, M: int, T: int):\n'
        '    for i in range(0, N):\n'
        '        w = 0.5 * A[i, 0:M]\n'
        '        for t in range(0, T):\n'
        '            w = w + 0.25 * (A[i + 1, 0:M] - w[0:M])\n'
        '        out[i] = np.dot(w[0:M], w[0:M])\n')
    fname = "<slicing-offset-kernel>"
    linecache.cache[fname] = (len(src), None, src.splitlines(True), fname)
    ns = {}
    exec(compile(src, fname, "exec"), ns)
    ck = compile_kernel(ns["kern"], enable_jax=False)
    units = pfor_units(ck)
    assert units
    assert "A" not in units[0].sliceable
    assert "out" in units[0].sliceable


# ---------------------------------------------------------------------------
# ChunkSlice wrapper semantics
# ---------------------------------------------------------------------------

def test_chunkslice_rebases_scalar_and_slice_keys():
    full = np.arange(24.0).reshape(8, 3)
    w = rebase_chunk(full[2:5].copy(), 2)
    assert np.array_equal(w[2], full[2])
    assert np.array_equal(w[4, 1:3], full[4, 1:3])
    assert np.array_equal(w[slice(2, 4)], full[2:4])
    w[3] = -1.0
    assert np.all(np.asarray(w)[1] == -1.0)


def test_chunkslice_derived_views_index_normally():
    w = rebase_chunk(np.arange(12.0).reshape(4, 3), 10)
    row = w[10]           # global row 10 → local row 0
    assert np.array_equal(np.asarray(row), [0.0, 1.0, 2.0])
    # arithmetic results and ravel views reset the base to 0
    assert float((row * 2)[0]) == 0.0
    assert float(w.ravel()[0]) == 0.0


def test_chunkslice_out_of_chunk_raises():
    w = rebase_chunk(np.arange(6.0).reshape(3, 2), 4)
    with pytest.raises(IndexError, match="below chunk base"):
        w[1]
    with pytest.raises(IndexError, match="leading axis"):
        w[np.array([4, 5])]


def test_chunkslice_survives_diff_machinery():
    """_chunk_updates must diff/restore through the wrapper."""
    full = np.zeros((6, 2))

    def make(out):
        def body(lo, hi):
            for i in range(lo, hi):
                out[i] = i + 1.0
        return body

    chunk = rebase_chunk(full[2:4].copy(), 2)
    body = make(chunk)
    updates = _chunk_updates(body, 2, 4, ("out",))
    idx, vals = updates["out"]
    assert list(idx) == [0, 1, 2, 3]          # chunk-local flat indices
    assert list(vals) == [3.0, 3.0, 4.0, 4.0]
    # restore-after-diff: the cached cell is pristine again
    assert np.all(np.asarray(chunk) == 0.0)


# ---------------------------------------------------------------------------
# head-side gather: re-basing + the lost-writes guard
# ---------------------------------------------------------------------------

def test_merge_updates_rebases_sliced_indices():
    arrays = {"out": np.zeros((6, 2))}
    spec = SimpleNamespace(lo=2, hi=4, sliced=("out",))
    # worker-local flat indices into its (2, 2) chunk
    ClusterRuntime._merge_updates(
        arrays, {"out": (np.array([1, 2]), np.array([5.0, 7.0]))}, spec)
    assert arrays["out"][2, 1] == 5.0
    assert arrays["out"][3, 0] == 7.0
    assert np.count_nonzero(arrays["out"]) == 2


def test_merge_updates_unknown_array_raises():
    arrays = {"out": np.zeros(4)}
    spec = SimpleNamespace(lo=0, hi=2, sliced=())
    with pytest.raises(ClusterTaskError, match="ghost"):
        ClusterRuntime._merge_updates(
            arrays, {"ghost": (np.array([0]), np.array([1.0]))}, spec)


# ---------------------------------------------------------------------------
# split serialization
# ---------------------------------------------------------------------------

def _make_body(data, out, scale):
    def body(lo, hi):
        for i in range(lo, hi):
            out[i] = data[i, 0] * scale[0] + data[i, 1]
    return body


def test_split_fn_partitions_cells():
    data = np.arange(20.0).reshape(10, 2)
    out = np.zeros(10)
    scale = np.array([3.0])
    body = _make_body(data, out, scale)
    parts = split_fn(body, sliceable=("data", "out"))
    assert set(parts.sliced) == {"data", "out"}
    assert set(parts.cell_pkls) == {"scale"}
    bcast, sliced = payload_split_nbytes(body, ("data", "out"))
    assert bcast == scale.nbytes
    assert sliced == data.nbytes + out.nbytes


def test_split_fn_key_stable_and_cells_content_hashed():
    data = np.arange(20.0).reshape(10, 2)
    out = np.zeros(10)
    scale = np.array([3.0])
    body = _make_body(data, out, scale)
    p1 = split_fn(body, sliceable=("data", "out"))
    p2 = split_fn(body, sliceable=("data", "out"))
    assert p1.blob_key == p2.blob_key
    assert p1.cell_hashes == p2.cell_hashes
    scale[0] = 5.0        # data change: same identity, changed cell
    p3 = split_fn(body, sliceable=("data", "out"))
    assert p3.blob_key == p1.blob_key
    assert p3.cell_hashes["scale"] != p1.cell_hashes["scale"]
    # a *shape* change is a different blob identity
    body2 = _make_body(np.arange(30.0).reshape(15, 2), np.zeros(15),
                       scale)
    assert split_fn(body2, ("data", "out")).blob_key != p1.blob_key


def test_assemble_fn_roundtrip_with_slices():
    data = np.arange(20.0).reshape(10, 2)
    out = np.zeros(10)
    scale = np.array([2.0])
    body = _make_body(data, out, scale)
    run_sliced_inprocess(body, 0, 10, ("out",), ("data", "out"))
    assert np.array_equal(out, data[:, 0] * 2.0 + data[:, 1])


# ---------------------------------------------------------------------------
# the property: sliced execution == broadcast execution, bitwise
# ---------------------------------------------------------------------------

def _equivalence_case(vec, dot, scal, oidx, n, t, seed):
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(n, n))
    B = rng.normal(size=n)
    C = rng.normal(size=n)

    rt = InProcessShards()
    ck = compile_template(vec, dot, scal, oidx, runtime=rt)
    ck.pfor_config.runtime = rt
    ck.pfor_config.distribute_threshold = 0

    out_sliced = np.zeros(n)
    ck.call_variant("np", A.copy(), B.copy(), C.copy(), out_sliced,
                    n, n, t)
    assert rt.calls, "kernel never reached the shards path"
    assert set(rt.calls[-1]) == expected_sliceable(vec, dot, scal, oidx)

    # broadcast run: same machinery, slicing disabled
    out_bcast = np.zeros(n)
    body_holder = {}

    class Bcast(InProcessShards):
        def pfor_shards(self, body, lo, hi, tile, *, written, sliceable,
                        est_flops):
            run_sliced_inprocess(body, lo, hi, written, ())

    ck.pfor_config.runtime = Bcast()
    ck.call_variant("np", A.copy(), B.copy(), C.copy(), out_bcast,
                    n, n, t)

    assert np.array_equal(out_sliced, out_bcast), \
        f"sliced != broadcast (bitwise) for {(vec, dot, scal, oidx)}"

    # and both match plain sequential execution of the original
    out_seq = np.zeros(n)
    ck.pfor_config.force_sequential = True
    try:
        ck.call_variant("np", A.copy(), B.copy(), C.copy(), out_seq,
                        n, n, t)
    finally:
        ck.pfor_config.force_sequential = False
    assert np.array_equal(out_sliced, out_seq)


@pytest.mark.parametrize("vec,dot,scal,oidx", GRID)
def test_sliced_matches_broadcast_bitwise(vec, dot, scal, oidx):
    _equivalence_case(vec, dot, scal, oidx, n=13, t=4, seed=11)


if HAVE_HYPOTHESIS:
    @given(st.sampled_from(VEC_PATTERNS), st.sampled_from(DOT_PATTERNS),
           st.sampled_from(SCAL_PATTERNS), st.sampled_from(OIDX_PATTERNS),
           st.integers(4, 24), st.integers(1, 6),
           st.integers(0, 2**31 - 1))
    @settings(max_examples=25, deadline=None)
    def test_property_random_affine_bodies(vec, dot, scal, oidx, n, t,
                                           seed):
        """Hypothesis widening of the grid: random shapes, iteration
        counts and data for every pattern combination."""
        _equivalence_case(vec, dot, scal, oidx, n, t, seed)
else:
    def test_property_random_affine_bodies_skipped():
        pytest.importorskip("hypothesis")


# ---------------------------------------------------------------------------
# cost model: sliced payload flips marginal kernels
# ---------------------------------------------------------------------------

def test_sliced_payload_flips_profitability():
    fleet = [DeviceProfile(wid=i, gflops=50.0, transport_mbs=200.0)
             for i in range(4)]
    # a marginal kernel: 2 GFLOP on a 10 GFLOP/s head = 0.2 s local;
    # the fleet computes it in 0.01 s but the 16 MB payload over a
    # 200 MB/s pipe costs 0.08 s once — or 0.32 s broadcast ×4
    flops, payload = 2e9, 16 * (1 << 20)
    assert not cost.cluster_distribute_profitable(
        flops, payload, fleet, n_chunks=4, local_gflops=10.0)
    # same bytes chunk-sliced ship once total: distribution now wins
    assert cost.cluster_distribute_profitable(
        flops, 0, fleet, n_chunks=4, local_gflops=10.0,
        sliced_bytes=payload)


# ---------------------------------------------------------------------------
# live cluster: the wire path end to end
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster():
    rt = ClusterRuntime(workers=2)
    yield rt
    rt.shutdown()


def test_cluster_sliced_pfor_matches_broadcast(cluster):
    rng = np.random.default_rng(9)
    data = rng.normal(size=(40, 32))
    out_s, out_b = np.zeros(40), np.zeros(40)

    def make(out, data):
        def body(lo, hi):
            for i in range(lo, hi):
                out[i] = float(data[i].sum()) * 1.5
        return body

    before = cluster.sliced_args
    cluster.pfor_shards(make(out_s, data), 0, 40,
                        written=("out",), sliceable=("data", "out"))
    assert cluster.sliced_args > before
    cluster.pfor_shards(make(out_b, data), 0, 40, written=("out",))
    assert np.array_equal(out_s, out_b)
    assert np.array_equal(out_s, data.sum(axis=1) * 1.5)


def test_cluster_compiled_kernel_slices_and_caches(cluster):
    ck = compile_template("A[i, 0:M]", "A[i, 0:M]", "C[i]", "i",
                          runtime=cluster)
    ck.pfor_config.runtime = cluster
    ck.pfor_config.distribute_threshold = 0
    rng = np.random.default_rng(3)
    n, t = 24, 5
    A, B, C = (rng.normal(size=(n, n)), rng.normal(size=n),
               rng.normal(size=n))
    outs = []
    saved0 = cluster.bytes_saved_sliced
    hits0, miss0 = cluster.blob_hits, cluster.blob_misses
    for _ in range(3):
        out = np.zeros(n)
        ck.call_variant("np", A, B, C, out, n, n, t)
        outs.append(out)
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[0], outs[2])
    assert cluster.bytes_saved_sliced > saved0
    assert cluster.blob_misses == miss0 + 1     # first call only
    assert cluster.blob_hits >= hits0 + 2       # every later call
