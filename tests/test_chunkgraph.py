"""Tests for the chunk-graph executor (sharded resolution), the v3
prefix-serving rescache, depth-incremental solving, and the finite
store-buffer model."""

import os

import numpy as np
import pytest

from repro.core import rescache as rc
from repro.core.simulator import (
    BatchedCacheSim, CacheConfig, MemAccess, MemoryModel, SimStage, acp,
    acp_cache, compose_stacks, hp_cache, simulate_conventional,
    simulate_dataflow, simulate_dataflow_many, simulate_processor,
)


@pytest.fixture()
def small_chunks(tmp_path, monkeypatch):
    """Fresh isolated store with a tiny canonical chunk grid, so
    multi-chunk behaviour (sharding, prefix serving, resume) is
    exercised at test-sized iteration counts."""
    d = str(tmp_path / "rescache")
    rc.clear()
    rc.configure(enabled=True, directory=d)
    monkeypatch.setattr(rc, "CHUNK_ITERS", 512)
    yield d
    rc.clear()
    rc.configure(enabled=False)


def _pipeline(n=5000, seed=5):
    rng = np.random.default_rng(seed)
    return [
        SimStage("addr", ii=1, latency=2,
                 accesses=[MemAccess("i", np.arange(n) * 4)]),
        SimStage("fetch", ii=1, latency=3,
                 accesses=[MemAccess("x", rng.integers(0, 1 << 19, n) * 4),
                           MemAccess("y", np.arange(n) * 4 + (1 << 22),
                                     is_store=True)]),
        SimStage("fma", ii=4, latency=6),
    ]


def _assert_same(a, b, what=""):
    assert a.cycles == b.cycles, what
    assert a.stage_stall_cycles == b.stage_stall_cycles, what
    assert (a.cache_hits, a.cache_misses) == \
        (b.cache_hits, b.cache_misses), what


# ---------------------------------------------------------------------------
# Cache-state transport: export / import / compose
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ways", [2, 4, 8])
def test_export_import_split_replay(ways):
    """Splitting a trace at any point and carrying the state through
    export→import must reproduce the straight replay exactly."""
    rng = np.random.default_rng(11)
    cfg = CacheConfig(size_bytes=4096, line_bytes=32, ways=ways)
    addrs = rng.integers(0, 1 << 14, 4000) * 4
    straight = BatchedCacheSim(cfg)
    want = straight.lookup(addrs)
    for cut in (1, 137, 2000, 3999):
        a = BatchedCacheSim(cfg)
        h0 = a.lookup(addrs[:cut])
        stacks, mt = a.export_stacks()
        b = BatchedCacheSim(cfg)
        b.import_stacks(stacks, mt)
        h1 = b.lookup(addrs[cut:])
        got = np.concatenate([h0, h1])
        assert np.array_equal(got, want), (ways, cut)


@pytest.mark.parametrize("ways", [2, 4])
def test_effect_composition(ways):
    """A chunk's own effect (replayed from empty) composed onto any
    incoming state equals replaying through that state — the monoid
    property the sharded resolver's phase A/compose relies on."""
    rng = np.random.default_rng(12)
    cfg = CacheConfig(size_bytes=2048, line_bytes=32, ways=ways)
    a1 = rng.integers(0, 1 << 12, 1500) * 4
    a2 = rng.integers(0, 1 << 12, 1500) * 4
    seq = BatchedCacheSim(cfg)
    seq.lookup(a1)
    seq.lookup(a2)
    want, _ = seq.export_stacks()
    first = BatchedCacheSim(cfg)
    first.lookup(a1)
    st1, _ = first.export_stacks()
    own = BatchedCacheSim(cfg)
    own.lookup(a2)
    st2, _ = own.export_stacks()
    assert np.array_equal(compose_stacks(st1, st2), want)


# ---------------------------------------------------------------------------
# Sharded resolution == streaming, bit for bit
# ---------------------------------------------------------------------------

def _sharded(*args, **kwargs):
    """simulate_dataflow_many via the pool, asserting the sharded path
    actually engaged (a silent fallback to streaming would make the
    equality tests vacuous)."""
    from repro.core import chunkgraph
    runs0 = chunkgraph._POOL_RUNS
    out = simulate_dataflow_many(*args, **kwargs)
    assert chunkgraph._POOL_RUNS == runs0 + 1, \
        "chunk-graph pool did not engage"
    return out


@pytest.mark.parametrize("workers", [2, 3])
def test_sharded_equals_streaming_no_cache(workers, monkeypatch):
    monkeypatch.setattr(rc, "CHUNK_ITERS", 512)
    rc.configure(enabled=False)
    stages = _pipeline()
    mems = {"ACP": acp(), "ACPC": acp_cache(), "HPC": hp_cache()}
    ref = simulate_dataflow_many(stages, dict(mems), 5000,
                                 fifo_depths=(4, 16), use_rescache=False)
    got = _sharded(stages, dict(mems), 5000,
                   fifo_depths=(4, 16), use_rescache=False,
                   workers=workers)
    for key in ref:
        _assert_same(got[key], ref[key], key)


def test_sharded_write_around_draw_positions(monkeypatch):
    """Write-around stores bypass the cache but still draw from the
    backing store: the sharded master's draw offsets must count them
    (misses alone under-count), or every later chunk's latencies
    shift."""
    monkeypatch.setattr(rc, "CHUNK_ITERS", 512)
    rc.configure(enabled=False)
    rng = np.random.default_rng(21)
    n = 5000
    stages = [
        SimStage("ld", ii=1, latency=2,
                 accesses=[MemAccess("x",
                                     rng.integers(0, 1 << 14, n) * 4)]),
        SimStage("st", ii=1, latency=2,
                 accesses=[MemAccess("y",
                                     rng.integers(0, 1 << 14, n) * 4,
                                     is_store=True)]),
    ]
    wa = MemoryModel(name="wa",
                     cache=CacheConfig(write_allocate=False))
    ref = simulate_dataflow_many(stages, {"wa": wa}, n,
                                 fifo_depths=(16,), use_rescache=False)
    got = _sharded(stages, {"wa": MemoryModel(
        name="wa", cache=CacheConfig(write_allocate=False))}, n,
        fifo_depths=(16,), use_rescache=False, workers=2)
    _assert_same(got[("wa", 16)], ref[("wa", 16)])


@pytest.mark.parametrize("chunk", [512, 1024])
def test_sharded_equals_streaming_with_store(chunk, small_chunks,
                                             monkeypatch):
    """Sharded (writing records) vs cold streaming, then a fully-served
    rerun — all bit-identical, and the rerun resolves nothing."""
    monkeypatch.setattr(rc, "CHUNK_ITERS", chunk)
    stages = _pipeline(seed=6)
    mems = {"ACP": acp(), "ACPC": acp_cache()}
    ref = simulate_dataflow_many(stages, dict(mems), 5000,
                                 fifo_depths=(16,), use_rescache=False)
    got = _sharded(stages, dict(mems), 5000,
                   fifo_depths=(16,), workers=2)
    for key in ref:
        _assert_same(got[key], ref[key], key)
    assert rc.census()["chunks"] > 0
    cold0 = rc.stats()["cold_chunks"]
    # fully served rerun: falls back to the cheap streaming fold+solve
    again = simulate_dataflow_many(stages, dict(mems), 5000,
                                   fifo_depths=(16,), workers=2)
    for key in ref:
        _assert_same(again[key], ref[key], key)
    assert rc.stats()["cold_chunks"] == cold0


@pytest.mark.slow
def test_sharded_paper_kernels_bit_identical(monkeypatch):
    """All four paper kernels, full-scale window-generated traces at a
    truncated count, multiple chunk sizes and worker counts: the
    sharded executor must match the streaming engine exactly."""
    from benchmarks.paper_fig5 import _dataflow_mems, _make_kernel, \
        build_stages
    rc.configure(enabled=False)
    for kname in ("spmv", "knapsack", "floyd_warshall", "dfs"):
        stages, _ = build_stages(_make_kernel(kname))
        n = 60_000
        mems = _dataflow_mems()
        ref = simulate_dataflow_many(stages, dict(mems), n,
                                     fifo_depths=(256,),
                                     use_rescache=False)
        for chunk, workers in ((16384, 2), (10000, 3)):
            monkeypatch.setattr(rc, "CHUNK_ITERS", chunk)
            got = simulate_dataflow_many(stages, dict(mems), n,
                                         fifo_depths=(256,),
                                         use_rescache=False,
                                         workers=workers)
            for key in ref:
                _assert_same(got[key], ref[key], (kname, chunk, key))


# ---------------------------------------------------------------------------
# Prefix serving and resume
# ---------------------------------------------------------------------------

def test_prefix_serves_any_shorter_run(small_chunks):
    """An N-iteration artifact serves every M ≤ N run — including
    mid-chunk M — with zero cold resolution and results identical to a
    cold M-iteration run (cycles, stalls, cache statistics)."""
    stages = _pipeline(seed=7)
    simulate_dataflow(stages, acp_cache(), 5000, fifo_depth=16)
    for m in (5000, 4608, 3000, 517, 40):
        cold = simulate_dataflow(stages, acp_cache(), m, fifo_depth=16,
                                 use_rescache=False)
        before = rc.stats()["cold_chunks"]
        served = simulate_dataflow(stages, acp_cache(), m, fifo_depth=16)
        _assert_same(served, cold, m)
        assert rc.stats()["cold_chunks"] == before, m


def test_conventional_and_processor_prefix_serving(small_chunks):
    """The conventional engine's stall fold and the processor's hit
    levels prefix-serve too (the Fig. 5 --quick regime)."""
    stages = _pipeline(seed=8)
    accs = [a for st in stages for a in st.accesses]
    simulate_conventional(stages, acp_cache(), 5000)
    simulate_processor(10.0, accs, 5000)
    for m in (5000, 3000, 700):
        cv_cold = simulate_conventional(stages, acp_cache(), m,
                                        use_rescache=False)
        p_cold = simulate_processor(10.0, accs, m, use_rescache=False)
        before = rc.stats()["cold_chunks"]
        cv = simulate_conventional(stages, acp_cache(), m)
        p = simulate_processor(10.0, accs, m)
        _assert_same(cv, cv_cold, m)
        assert (p.cycles, p.cache_hits, p.cache_misses) == \
            (p_cold.cycles, p_cold.cache_hits, p_cold.cache_misses), m
        assert rc.stats()["cold_chunks"] == before, m
    # posted_writes is fold-only for the conventional artifact: the
    # blocking-store variant serves from the same records
    blocking = acp_cache()
    blocking.posted_writes = False
    cv_cold = simulate_conventional(stages, blocking, 5000,
                                    use_rescache=False)
    before = rc.stats()["cold_chunks"]
    cv = simulate_conventional(stages, blocking, 5000)
    _assert_same(cv, cv_cold)
    assert rc.stats()["cold_chunks"] == before


def test_resume_from_interrupted_run(small_chunks):
    """A run that stopped partway leaves completed chunk records; the
    next run resolves only the missing chunks and is bit-identical to
    an uninterrupted cold run."""
    stages = _pipeline(seed=9)
    cold = simulate_dataflow(stages, acp_cache(), 5000, fifo_depth=16,
                             use_rescache=False)
    simulate_dataflow(stages, acp_cache(), 1500, fifo_depth=16)
    before = rc.stats()["cold_chunks"]
    full = simulate_dataflow(stages, acp_cache(), 5000, fifo_depth=16)
    _assert_same(full, cold)
    # chunks 0-1 (full 512-records) were resumed over; 8 of 10 resolve
    assert rc.stats()["cold_chunks"] - before == 8
    # resume works for the sharded executor too
    rc.clear(disk=True)
    rc.configure(enabled=True)
    simulate_dataflow(stages, acp_cache(), 1500, fifo_depth=16)
    sharded = simulate_dataflow_many(stages, {"M": acp_cache()}, 5000,
                                     fifo_depths=(16,),
                                     workers=2)[("M", 16)]
    _assert_same(sharded, cold)


def test_resume_after_missing_middle_chunk(small_chunks):
    """A gap in the stored chunks (evicted mid-prefix) truncates the
    usable prefix; the run re-resolves from the gap, still exact."""
    stages = _pipeline(seed=10)
    cold = simulate_dataflow(stages, acp_cache(), 5000, fifo_depth=16,
                             use_rescache=False)
    simulate_dataflow(stages, acp_cache(), 5000, fifo_depth=16)
    # knock out chunk 3 on disk and in memory
    victims = [f for f in os.listdir(small_chunks)
               if f.endswith(".c00003.npz")]
    assert victims
    for f in victims:
        os.unlink(os.path.join(small_chunks, f))
    rc._mem.clear()
    rc._mem_bytes = 0
    again = simulate_dataflow(stages, acp_cache(), 5000, fifo_depth=16)
    _assert_same(again, cold)


# ---------------------------------------------------------------------------
# Depth-incremental solving
# ---------------------------------------------------------------------------

def test_depth_incremental_equals_cold_at_every_depth():
    """Warm-started depth grids must equal cold per-depth solves even
    when shallow depths bind backpressure (Gauss–Seidel / block-mode
    territory), cycles and stall buckets alike."""
    rc.configure(enabled=False)
    stages = _pipeline(seed=13)
    depths = (2, 3, 8, 64)
    warm = simulate_dataflow_many(stages, {"M": acp_cache()}, 4000,
                                  fifo_depths=depths, use_rescache=False)
    cold = simulate_dataflow_many(stages, {"M": acp_cache()}, 4000,
                                  fifo_depths=depths, use_rescache=False,
                                  depth_incremental=False)
    for d in depths:
        _assert_same(warm[("M", d)], cold[("M", d)], d)
        ref = simulate_dataflow(stages, acp_cache(), 4000, fifo_depth=d,
                                use_rescache=False, reference=True)
        _assert_same(warm[("M", d)], ref, d)


# ---------------------------------------------------------------------------
# Finite store buffer
# ---------------------------------------------------------------------------

def test_store_buffer_pushback_monotone_and_mirrored():
    """Shrinking the posted-write buffer can only slow the pipeline
    (pushback through max_outstanding), ``None`` equals a buffer at
    least as deep as the outstanding cap, and the scalar reference
    mirrors the vectorized fold exactly at every depth."""
    rc.configure(enabled=False)
    rng = np.random.default_rng(14)
    n = 3000
    stages = [
        SimStage("w", ii=1, latency=2,
                 accesses=[MemAccess("out",
                                     rng.integers(0, 1 << 20, n) * 4,
                                     is_store=True)]),
        SimStage("mix", ii=1, latency=2,
                 accesses=[MemAccess("x",
                                     rng.integers(0, 1 << 20, n) * 4),
                           MemAccess("y", np.arange(n) * 4 + (1 << 23),
                                     is_store=True)]),
        SimStage("c", ii=2, latency=4),
    ]
    prev = None
    for depth in (None, 64, 8, 4, 2, 1):
        mem = MemoryModel(name=f"sb{depth}", store_buffer_depth=depth)
        vec = simulate_dataflow(stages, mem, n, use_rescache=False)
        ref = simulate_dataflow(stages, mem, n, reference=True)
        _assert_same(vec, ref, depth)
        if prev is not None:
            assert vec.cycles >= prev, depth
        prev = vec.cycles
    deep = MemoryModel(name="deep", store_buffer_depth=64)
    inf = MemoryModel(name="inf", store_buffer_depth=None)
    assert simulate_dataflow(stages, deep, n, use_rescache=False).cycles \
        == simulate_dataflow(stages, inf, n, use_rescache=False).cycles
    # fold-only: the buffer depth never keys the resolution artifact
    k1 = rc.resolution_key("dataflow", stages, deep, 0)
    k2 = rc.resolution_key("dataflow", stages,
                           MemoryModel(name="sb1",
                                       store_buffer_depth=1), 0)
    assert k1 == k2
    # blocking stores have no write buffer: depth is irrelevant
    b1 = MemoryModel(name="b1", posted_writes=False, store_buffer_depth=1)
    b2 = MemoryModel(name="b2", posted_writes=False)
    assert simulate_dataflow(stages, b1, n, use_rescache=False).cycles \
        == simulate_dataflow(stages, b2, n, use_rescache=False).cycles


# ---------------------------------------------------------------------------
# Store hygiene: gc and the census
# ---------------------------------------------------------------------------

def test_gc_removes_orphans_and_enforces_cap(small_chunks):
    stages = _pipeline(seed=15)
    simulate_dataflow(stages, acp_cache(), 5000, fifo_depth=16)
    chunks_before = rc.census()["chunks"]
    assert chunks_before > 0
    # plant v1/v2-era orphans: whole-run npz, json summary, tmp debris
    fake = "ab" * 16
    for name in (fake + ".npz", fake + ".json", "x.tmp"):
        with open(os.path.join(small_chunks, name), "wb") as f:
            f.write(b"\x00" * 2048)
    report = rc.gc()
    assert report["orphans_removed"] == 3
    assert rc.census()["chunks"] == chunks_before
    served = simulate_dataflow(stages, acp_cache(), 5000, fifo_depth=16)
    assert served.cycles > 0  # records survived the gc
    # byte cap: evict down to a single chunk's worth
    one = min(os.path.getsize(os.path.join(small_chunks, f))
              for f in os.listdir(small_chunks))
    report = rc.gc(max_bytes=one)
    assert report["evicted"] > 0
    assert report["remaining_bytes"] <= one
    # a gutted store degrades to cold resolution, not an error
    cold = simulate_dataflow(stages, acp_cache(), 5000, fifo_depth=16,
                             use_rescache=False)
    again = simulate_dataflow(stages, acp_cache(), 5000, fifo_depth=16)
    _assert_same(again, cold)


# ---------------------------------------------------------------------------
# The one chunk-resolution protocol: worker job object and sequencer
# ---------------------------------------------------------------------------

def _npz_arrays(path):
    with np.load(path) as z:
        return {k: z[k] for k in z.files}


@pytest.mark.parametrize("order", ["pool", "daemon"])
def test_chunk_job_matches_streaming(order, small_chunks, tmp_path):
    """The worker's per-job object, driven in process through the
    sequencer in the pool's phase order (A0 B0 C0 A1 B1 C1 …) or the
    daemon's interleaved one (A0 A1 … B0 B1 … C0 C1 …), gives the
    streaming resolver's replay deltas and latency matrices, and chunk
    records equal to the streaming engine's, array for array."""
    from test_engine import _two_stage

    from repro.core.chunkgraph import ChunkJob, ChunkSequencer
    from repro.core.simulator import _SharedResolver
    C, n = rc.CHUNK_ITERS, 2048
    stages = _two_stage(n, 4, store_heavy=True)

    def mems():
        return {"A": acp_cache(), "H": hp_cache(), "U": acp(),
                "W": MemoryModel(name="w", cache=CacheConfig(
                    write_allocate=False))}

    ref = _SharedResolver(stages, mems(), seed=0, capture=True)
    want_deltas, want_ops = {}, {}
    for k in range(n // C):
        want_deltas[k] = ref.replay(k * C, (k + 1) * C)
        ref.finish(k * C, (k + 1) * C, fold=False)
        want_ops[k] = {mn: L.copy() for mn, L in ref.last_ops.items()}
    stream_dir = str(tmp_path / "stream")
    rc.clear()
    rc.configure(enabled=True, directory=stream_dir)
    simulate_dataflow_many(stages, mems(), n, fifo_depths=(8,))

    job_dir = str(tmp_path / "job")
    rc.clear()
    rc.configure(enabled=True, directory=job_dir)
    m = mems()
    keys = {mn: rc.resolution_key("dataflow", stages, mem, 0)
            for mn, mem in m.items()}
    job = ChunkJob({"stages": stages, "mems": m, "seed": 0,
                    "n_iters": n, "keys": keys})
    seq = ChunkSequencer(0, m, 0, None, {mn: 0 for mn in m},
                         {g: (0, 0) for g in job.resolver.caches})
    got_deltas, cums = {}, {}

    def deliver(msgs):
        for kind, _, k, *args in msgs:
            if kind == "task":
                seq.effect(k, *job.effect(k, *args))
            elif kind == "state":
                got_deltas[k] = job.finalize(k, *args)
                seq.replay(k, got_deltas[k])
            else:
                cums[k], inline = job.draws(k, *args)
                assert all(v is None for v in inline.values())
                seq.done(k)

    chunks = range(n // C)
    if order == "pool":
        for k in chunks:
            deliver([seq.task(k, k * C, (k + 1) * C)])
            deliver(seq.pump(k + 1))  # state k
            deliver(seq.pump(k + 1))  # draws k
    else:
        deliver([seq.task(k, k * C, (k + 1) * C) for k in chunks])
        deliver(seq.pump(len(chunks)))  # every state
        deliver(seq.pump(len(chunks)))  # every draws
    assert sorted(cums) == list(chunks)
    assert got_deltas == want_deltas
    for mn, key in keys.items():
        for k in chunks:
            rec = rc.get_chunk(key, k)
            assert np.array_equal(rec.ops.astype(np.int32),
                                  want_ops[k][mn]), (mn, k)
            name = os.path.basename(rc._chunk_path(job_dir, key, k))
            a = _npz_arrays(os.path.join(job_dir, name))
            b = _npz_arrays(os.path.join(stream_dir, name))
            assert sorted(a) == sorted(b), (mn, k)
            for f in a:
                assert a[f].dtype == b[f].dtype, (mn, k, f)
                assert np.array_equal(a[f], b[f]), (mn, k, f)


def _seq_fixture():
    """A sequencer over four chunks of a tiny pipeline, with the chunks'
    own effects and the replay deltas a warm streaming replay gives."""
    from repro.core.chunkgraph import ChunkSequencer, _compose_state
    from repro.core.simulator import _SharedResolver
    C, K = 512, 4
    stages = _pipeline(C * K, seed=3)
    mems = {"A": acp_cache(), "U": acp()}
    own = _SharedResolver(stages, mems, 0)
    effects = [own.chunk_effects(k * C, (k + 1) * C) for k in range(K)]
    warm = _SharedResolver(stages, mems, 0)
    deltas = [warm.replay(k * C, (k + 1) * C) for k in range(K)]
    states = [None]
    for eff, _ in effects:
        states.append(_compose_state(states[-1], eff))
    seq = ChunkSequencer(7, mems, 0, None, {mn: 0 for mn in mems},
                         {g: (0, 0) for g in own.caches})
    tasks = [seq.task(k, k * C, (k + 1) * C) for k in range(K)]
    return seq, tasks, effects, deltas, states


def _same_state(a, b):
    assert sorted(a or {}) == sorted(b or {})
    for g in a or {}:
        assert np.array_equal(a[g][0], b[g][0]) and a[g][1] == b[g][1]


@pytest.mark.parametrize("case", ["out_of_order", "duplicate", "resend"])
def test_sequencer(case):
    """The master-side sequencer: effects arriving out of order compose
    in chunk order; a duplicate effect or replay (a resent chunk's) is
    ignored; a chunk's resend log is exactly the messages first sent
    for it, until its ``done``."""
    seq, tasks, effects, deltas, states = _seq_fixture()
    K = len(tasks)
    if case == "out_of_order":
        sent = []
        for k in (2, 0, 3, 1):
            seq.effect(k, *effects[k])
            sent += seq.pump(K)
        assert [(m[0], m[2]) for m in sent] == \
            [("state", 0), ("state", 1), ("state", 2), ("state", 3)]
        for k, m in enumerate(sent):
            assert m[1] == 7
            _same_state(m[3], states[k])
        return
    for k in range(K):
        seq.effect(k, *effects[k])
    if case == "duplicate":
        bogus = (effects[3][0], effects[1][1] + 12345)
        first = seq.pump(K)
        seq.effect(1, *bogus)  # resent chunk's effect: composed already
        for k in range(K):
            seq.replay(k, deltas[k])
        draws = seq.pump(K)
        seq.replay(0, deltas[1])  # after its draws went out
        seq.effect(0, *bogus)
        assert seq.pump(K) == []
        for k, m in enumerate(first):
            _same_state(m[3], states[k])
        cum = {"A": 0, "U": 0}
        for k, m in enumerate(draws):
            assert m[:3] == ("draws", 7, k)
            assert m[3]["A"]["base"] == cum["A"]
            assert m[3]["U"]["base"] == cum["U"]
            geo = seq.geos["A"]
            cum["A"] += deltas[k][geo][2]
            cum["U"] += effects[k][1]
        return
    seq.replay(0, deltas[0])
    sent = seq.pump(2)  # states 0, 1 and draws 0
    assert seq.resend(0) == [tasks[0], sent[0], sent[2]]
    assert seq.in_phase_c(0)
    assert seq.resend(1) == [tasks[1], sent[1]]
    assert not seq.in_phase_c(1)
    assert seq.resend(2) == [tasks[2]]
    seq.done(0)
    assert not seq.in_phase_c(0)
    assert seq.resend(1) == [tasks[1], sent[1]]
