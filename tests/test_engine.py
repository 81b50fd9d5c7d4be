"""Tests for the backend-switchable resolution engine (repro.core.engine):
kernel parity (numpy vs jax, bit-exact), the fused effect+replay pass,
cycle-exactness across engines × execution modes vs the scalar
reference, effect-record persistence, and the per-phase wall accounting.
"""

import os

import numpy as np
import pytest

from repro.core import engine as eng
from repro.core import rescache as rc
from repro.core.simulator import (
    BatchedCacheSim, CacheConfig, MemAccess, SimStage, _resolve_fused,
    _SharedResolver, acp, acp_cache, compose_stacks, hp_cache,
    simulate_dataflow, simulate_dataflow_many,
)

HAVE_JAX = eng.jax_modules() is not None
needs_jax = pytest.mark.skipif(not HAVE_JAX, reason="jax not importable")


@pytest.fixture(autouse=True)
def _clean_engine():
    """Every test starts from the env-driven default and leaves no
    forced selection or wall residue behind."""
    eng.select(None)
    eng.reset_walls()
    eng.reset_dispatches()
    yield
    eng.select(None)
    eng.reset_walls()
    eng.reset_dispatches()


# ---------------------------------------------------------------------------
# Selection layer
# ---------------------------------------------------------------------------

def test_env_selection(monkeypatch):
    monkeypatch.setenv("REPRO_ENGINE", "numpy")
    assert eng.current() == "numpy"
    monkeypatch.setenv("REPRO_ENGINE", "nonsense")
    assert eng.current() in ("numpy", "jax")  # falls back to auto
    monkeypatch.delenv("REPRO_ENGINE")
    assert eng.current() in ("numpy", "jax")


def test_select_and_use_override(monkeypatch):
    monkeypatch.setenv("REPRO_ENGINE", "numpy")
    eng.select("numpy")
    assert eng.current() == "numpy"
    if HAVE_JAX:
        with eng.use("jax"):
            assert eng.current() == "jax"
            assert eng._explicit()
            with eng.use("numpy"):  # nesting restores the outer override
                assert eng.current() == "numpy"
            assert eng.current() == "jax"
    assert eng.current() == "numpy"
    with pytest.raises(ValueError):
        eng.select("cuda")
    with pytest.raises(ValueError):
        with eng.use("tpu"):
            pass


def test_jax_without_jax_degrades(monkeypatch):
    """An explicit jax selection on a host without jax raises instead of
    quietly running numpy; ``auto`` still degrades to numpy there."""
    monkeypatch.setattr(eng, "_jax_mods", False)
    eng.select("jax")
    with pytest.raises(RuntimeError, match="jax"):
        eng.current()
    eng.select("auto")
    assert eng.current() == "numpy"


def _child_env(q):
    q.put({k: os.environ.get(k) for k in eng.CHILD_ENV})


def test_cpu_children_env(monkeypatch):
    """Spawned children start with JAX_PLATFORMS=cpu and the numpy
    engine (the parent may hold the chip); the parent's environment is
    restored afterwards."""
    import multiprocessing
    monkeypatch.setenv("REPRO_ENGINE", "jax")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    ctx = multiprocessing.get_context("spawn")
    q = ctx.Queue()
    p = ctx.Process(target=_child_env, args=(q,))
    with eng.cpu_children():
        p.start()
    try:
        assert q.get(timeout=120) == eng.CHILD_ENV
    finally:
        p.join(timeout=60)
    assert os.environ["REPRO_ENGINE"] == "jax"
    assert "JAX_PLATFORMS" not in os.environ


# ---------------------------------------------------------------------------
# Per-phase wall accounting
# ---------------------------------------------------------------------------

def test_walls_accumulate_and_merge():
    with eng.phase("replay"):
        pass
    with eng.phase("replay"):
        pass
    with eng.phase("solve"):
        pass
    w = eng.walls()
    assert set(w) == {"replay", "solve"} and all(v >= 0 for v in w.values())
    eng.merge_walls({"replay": 1.5, "fold": 2.0})
    w2 = eng.walls()
    assert w2["replay"] >= 1.5 and w2["fold"] == 2.0
    eng.merge_walls(None)  # tolerated: workers may report no walls
    eng.reset_walls()
    assert eng.walls() == {}


# ---------------------------------------------------------------------------
# running_max parity
# ---------------------------------------------------------------------------

def _rmax_cases():
    rng = np.random.default_rng(0)
    B = eng._RMAX_BLOCK
    yield np.arange(10, dtype=np.int64)                    # tiny
    yield rng.integers(0, 1 << 40, B - 1)                  # below one block
    yield rng.integers(0, 1 << 40, 2 * B)                  # exact blocks
    yield rng.integers(0, 1 << 40, 5 * B + 137)            # ragged tail
    yield np.arange(4 * B, dtype=np.int64)                 # worst case: rising
    yield -np.arange(4 * B, dtype=np.int64)                # best case: falling
    yield np.full(3 * B + 7, 42, dtype=np.int64)           # constant
    a = rng.integers(0, 1 << 20, 3 * B).astype(np.int32)   # int32 input
    yield a
    big = rng.integers(1 << 33, 1 << 40, 2 * B + 11)       # tags > 2**31
    yield big


@pytest.mark.parametrize("i,a", list(enumerate(_rmax_cases())))
def test_running_max_np_parity(i, a):
    want = np.maximum.accumulate(a)
    got = eng._running_max_np(a.copy())
    assert got.dtype == a.dtype
    assert np.array_equal(got, want), f"case {i}"


def test_running_max_noncontiguous_falls_back():
    rng = np.random.default_rng(1)
    base = rng.integers(0, 1 << 30, 8 * eng._RMAX_BLOCK)
    view = base[::2]  # non-contiguous: must take the plain accumulate
    assert not view.flags.c_contiguous
    want = np.maximum.accumulate(view.copy())
    assert np.array_equal(eng._running_max_np(view), want)


@needs_jax
def test_running_max_jax_parity():
    rng = np.random.default_rng(2)
    for n in (eng.JIT_MIN_ELEMS, eng.JIT_MIN_ELEMS * 3 + 17):
        a = rng.integers(0, 1 << 40, n)  # > 2**31: x64 must hold
        want = np.maximum.accumulate(a)
        with eng.use("jax"):
            got = eng.running_max(a.copy())
        assert got.dtype == np.int64
        assert np.array_equal(got, want)
    assert eng.dispatches() == {"cummax@cpu": 2}


@needs_jax
def test_pallas_running_max_interpret():
    """The TPU kernel's int32 block scan, run by the Pallas interpreter:
    ragged tails, several blocks (the carry), negative values and the
    int32 minimum used as padding."""
    rng = np.random.default_rng(3)
    lo, hi = np.iinfo(np.int32).min, np.iinfo(np.int32).max
    cases = [rng.integers(lo, hi, 5000, dtype=np.int64).astype(np.int32),
             (-3 * np.arange(3 * 16 * 128 + 5)).astype(np.int32),
             np.arange(2 * 16 * 128, dtype=np.int32),
             np.full(300, lo, np.int32),
             np.array([7], np.int32)]
    for a in cases:
        got = eng.pallas_running_max(a, block_rows=16, interpret=True)
        assert got.dtype == np.int32
        assert np.array_equal(got, np.maximum.accumulate(a))
    with pytest.raises(TypeError):
        eng.pallas_running_max(cases[0].astype(np.int64), interpret=True)
    assert eng.dispatches()["pallas_running_max@cpu"] == len(cases)


# ---------------------------------------------------------------------------
# N-way replay core parity (numpy vs jax, adversarial geometries)
# ---------------------------------------------------------------------------

def _addr_patterns(sim: BatchedCacheSim, n: int, seed: int):
    """Adversarial address streams for one geometry: single-set
    thrashing, a cyclic ways+1 working set (classic LRU worst case),
    skewed reuse, segment-boundary runs, and uniform random."""
    rng = np.random.default_rng(seed)
    lb, ns, ways = sim.cfg.line_bytes, sim.n_sets, sim.cfg.ways
    stride = lb * ns  # same set, new tag
    yield "one_set", (rng.integers(0, 3 * ways, n) * stride)
    cyc = (np.arange(n) % (ways + 1)) * stride
    yield "cyclic", cyc
    zipf = np.minimum(rng.zipf(1.3, n), 4 * ways) * lb
    yield "skewed", zipf
    runs = np.repeat(rng.integers(0, 8 * ways, max(1, n // 7)), 7)[:n]
    yield "runs", runs * lb
    yield "uniform", rng.integers(0, 1 << 22, n) * lb


@needs_jax
@pytest.mark.parametrize("ways", [3, 4, 8, 16])
def test_nway_jax_parity(ways):
    cfg = CacheConfig(size_bytes=ways * 16 * 32, line_bytes=32, ways=ways)
    probe = BatchedCacheSim(cfg)
    for name, addrs in _addr_patterns(probe, 4000, seed=ways):
        s_np = BatchedCacheSim(cfg)
        eng.select("numpy")
        h_np = s_np.lookup(addrs)
        st_np = s_np.export_stacks()
        s_jx = BatchedCacheSim(cfg)
        eng.select("jax")  # explicit: bypasses the size threshold
        h_jx = s_jx.lookup(addrs)
        st_jx = s_jx.export_stacks()
        eng.select(None)
        assert np.array_equal(h_jx, h_np), (ways, name)
        assert np.array_equal(st_jx[0], st_np[0]), (ways, name)
        assert st_jx[1] == st_np[1]


@needs_jax
def test_nway_jax_parity_large_tags():
    """Carried tags past 2**31 survive the jax path (x64 regression)."""
    cfg = CacheConfig(size_bytes=4 * 4 * 32, line_bytes=32, ways=4)
    probe = BatchedCacheSim(cfg)
    stride = probe.cfg.line_bytes * probe.n_sets
    rng = np.random.default_rng(9)
    addrs = (rng.integers(1 << 33, 1 << 36, 2000)) * stride
    s_np, s_jx = BatchedCacheSim(cfg), BatchedCacheSim(cfg)
    eng.select("numpy")
    h_np = s_np.lookup(addrs)
    eng.select("jax")
    h_jx = s_jx.lookup(addrs)
    eng.select(None)
    assert s_np._max_tag > (1 << 31)
    assert np.array_equal(h_jx, h_np)
    assert np.array_equal(s_jx.export_stacks()[0], s_np.export_stacks()[0])


# ---------------------------------------------------------------------------
# Fused effect+replay correctness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ways", [2, 4, 8])
def test_fused_lookup_matches_warm_lookup(ways):
    """fused_lookup + _resolve_fused against ANY incoming state equals
    a plain warm lookup, and the composed outgoing state matches the
    sequential one — the theorem the single-pass executor rests on."""
    cfg = CacheConfig(size_bytes=ways * 8 * 32, line_bytes=32, ways=ways)
    rng = np.random.default_rng(ways)
    warm = rng.integers(0, 1 << 14, 3000) * 4
    chunk = rng.integers(0, 1 << 14, 2500) * 4

    ref = BatchedCacheSim(cfg)
    ref.lookup(warm)
    incoming = ref.export_stacks()
    want_h = ref.lookup(chunk)
    want_out = ref.export_stacks()

    fus = BatchedCacheSim(cfg)
    h, amb = fus.fused_lookup(chunk)
    own = fus.export_stacks()
    # empty-incoming flags are exact as-is
    fresh = BatchedCacheSim(cfg)
    assert np.array_equal(h, fresh.lookup(chunk))
    # patched against the warm incoming state
    h = h.copy()
    if len(amb.idx):
        h[amb.idx] = _resolve_fused(amb, incoming[0], ways)
    assert np.array_equal(h, want_h)
    out = compose_stacks(incoming[0], own[0])
    assert np.array_equal(out, want_out[0])
    assert max(incoming[1], own[1]) == want_out[1]


def _two_stage(n, seed, store_heavy=False):
    rng = np.random.default_rng(seed)
    acc = [MemAccess("x", rng.integers(0, 1 << 16, n) * 4)]
    if store_heavy:
        acc.append(MemAccess("y", rng.integers(0, 1 << 16, n) * 4,
                             is_store=True))
    return [SimStage("ld", ii=1, latency=2, accesses=acc),
            SimStage("fma", ii=2, latency=4)]


@pytest.mark.parametrize("store_heavy", [False, True])
def test_chunk_effects_fused_equals_replay(store_heavy):
    """The fused single-pass resolver chunk chain (effects →
    finalize_replay) reproduces the two-pass resolver's deltas, hit
    flags, and cache state, chunk by chunk — including write-around
    stores that bypass the cache."""
    n, c = 3000, 1000
    mems = {"A": acp_cache(), "H": hp_cache()}
    seq = _SharedResolver(_two_stage(n, 4, store_heavy), mems, seed=0)
    fus = _SharedResolver(_two_stage(n, 4, store_heavy),
                          {"A": acp_cache(), "H": hp_cache()}, seed=0)
    states = None
    for lo in range(0, n, c):
        hi = min(n, lo + c)
        d_seq = seq.replay(lo, hi)
        eff, na = fus.chunk_effects_fused(lo, hi)
        assert set(eff) == set(fus.caches)
        assert na == fus._n_addrs
        d_fus = fus.finalize_replay(states)
        assert d_fus == d_seq
        for key in seq.caches:
            assert np.array_equal(fus._hits_by_key[key],
                                  seq._hits_by_key[key]), (lo, key)
            assert np.array_equal(fus.caches[key].export_stacks()[0],
                                  seq.caches[key].export_stacks()[0])
        states = {key: fus.caches[key].export_stacks()
                  for key in fus.caches}


def test_chunk_effects_fused_matches_chunk_effects():
    """Phase A's output (the own-effect monoid) is unchanged by the
    fusion — the persisted effect records are the same either way."""
    n = 2000
    r1 = _SharedResolver(_two_stage(n, 5), {"A": acp_cache()}, seed=0)
    r2 = _SharedResolver(_two_stage(n, 5), {"A": acp_cache()}, seed=0)
    e1, na1 = r1.chunk_effects(0, n)
    e2, na2 = r2.chunk_effects_fused(0, n)
    assert na1 == na2 and set(e1) == set(e2)
    for k in e1:
        assert np.array_equal(e1[k][0], e2[k][0])
        assert e1[k][1] == e2[k][1]


# ---------------------------------------------------------------------------
# Cycle-exactness: engines × execution modes vs the scalar reference
# ---------------------------------------------------------------------------

def _paper_pipeline(n, seed=11):
    rng = np.random.default_rng(seed)
    return [
        SimStage("addr", ii=1, latency=2,
                 accesses=[MemAccess("i", np.arange(n) * 4)]),
        SimStage("fetch", ii=1, latency=3,
                 accesses=[MemAccess("x", rng.integers(0, 1 << 18, n) * 4),
                           MemAccess("w", rng.integers(0, 1 << 12, n) * 4)]),
        SimStage("fma", ii=6, latency=8),
        SimStage("store", ii=1, latency=2,
                 accesses=[MemAccess("y", np.arange(n) * 4 + (1 << 22),
                                     is_store=True)]),
    ]


def _sig(r):
    return (r.cycles, r.cache_hits, r.cache_misses, r.stage_stall_cycles)


@pytest.fixture()
def small_chunks(tmp_path, monkeypatch):
    d = str(tmp_path / "rescache")
    rc.clear()
    rc.configure(enabled=True, directory=d)
    monkeypatch.setattr(rc, "CHUNK_ITERS", 512)
    yield d
    rc.clear()
    rc.configure(enabled=False)


@pytest.mark.parametrize("mem_mk", [acp, acp_cache, hp_cache])
def test_cycle_exact_engines_vs_reference(mem_mk):
    """numpy and jax streaming engines both equal the scalar reference
    simulator, cycle for cycle, on a paper-shaped pipeline."""
    n = 1500
    stages = _paper_pipeline(n)
    ref = simulate_dataflow(stages, mem_mk(), n, reference=True,
                            use_rescache=False)
    got_np = simulate_dataflow(stages, mem_mk(), n, use_rescache=False,
                               engine="numpy")
    assert _sig(got_np) == _sig(ref)
    if HAVE_JAX:
        got_jx = simulate_dataflow(stages, mem_mk(), n,
                                   use_rescache=False, engine="jax")
        assert _sig(got_jx) == _sig(ref)


@pytest.mark.parametrize(
    "engine",
    ["numpy"] + (["jax"] if HAVE_JAX else []))
def test_cycle_exact_sharded_vs_streaming(small_chunks, engine):
    """The chunk-graph executor (fused effect+replay in numpy workers,
    fold and solve on the master's engine) stays bit-identical to
    streaming on both backends."""
    n = 4 * 512
    stages = _paper_pipeline(n)
    mems = {"ACPC": acp_cache(), "HPC": hp_cache()}
    ref = simulate_dataflow_many(
        _paper_pipeline(n), {"ACPC": acp_cache(), "HPC": hp_cache()}, n,
        fifo_depths=(8,), use_rescache=False, engine=engine)
    rc.clear()
    got = simulate_dataflow_many(stages, mems, n, fifo_depths=(8,),
                                 workers=2, engine=engine)
    assert set(got) == set(ref)
    for k in ref:
        assert _sig(got[k]) == _sig(ref[k]), k


def test_cycle_exact_served(small_chunks):
    """Daemon-served resolution (numpy workers, see ``CHILD_ENV``)
    equals the library engine under the session's default backend."""
    import contextlib
    import tempfile

    from repro.serve.client import simulate_dataflow_served
    from repro.serve.daemon import ResolutionDaemon

    n = 3 * 512
    stages = _paper_pipeline(n)
    mems = {"ACPC": acp_cache()}
    ref = simulate_dataflow_many(_paper_pipeline(n),
                                 {"ACPC": acp_cache()}, n,
                                 fifo_depths=(8,), use_rescache=False)
    rc.clear()
    sdir = tempfile.mkdtemp(prefix="serve-")
    d = ResolutionDaemon(address=os.path.join(sdir, "d.sock"), workers=2)
    d.start()
    with contextlib.ExitStack() as st:
        st.callback(d.stop)
        got = simulate_dataflow_served(stages, mems, n, fifo_depths=(8,),
                                       address=d.address)
    for k in ref:
        assert _sig(got[k]) == _sig(ref[k]), k


# ---------------------------------------------------------------------------
# Effect-record persistence (satellite a)
# ---------------------------------------------------------------------------

@pytest.fixture()
def estore(tmp_path):
    d = str(tmp_path / "store")
    rc.clear()
    rc.configure(enabled=True, directory=d)
    yield d
    rc.clear()
    rc.configure(enabled=False)


def _an_effect(seed=0, big=False):
    rng = np.random.default_rng(seed)
    lo, hi = ((1 << 33), (1 << 35)) if big else (0, 1 << 12)
    stacks = rng.integers(lo, hi, (64, 4))
    stacks[rng.random(stacks.shape) < 0.2] = -1
    return np.sort(stacks, axis=1)[:, ::-1].copy(), int(stacks.max())


def test_effect_record_roundtrip(estore):
    key = "ab" * 16
    stacks, mt = _an_effect()
    rc.put_effect(key, 3, (stacks, mt), n_addrs=777)
    got = rc.get_effect(key, 3)
    assert got is not None
    gs, gmt, gna = got
    assert gs.dtype == np.int64 and np.array_equal(gs, stacks)
    assert (gmt, gna) == (mt, 777)
    assert rc.get_effect(key, 4) is None
    assert rc.get_effect("cd" * 16, 3) is None
    c = rc.census()
    assert c["effects"]["count"] == 1 and c["effects"]["bytes"] > 0
    assert c["effects"]["stores"] >= 1 and c["effects"]["hits"] >= 1


def test_effect_record_wide_tags(estore):
    """Tags past 2**31 skip the int32 narrowing and survive exactly."""
    key = "ef" * 16
    stacks, mt = _an_effect(1, big=True)
    rc.put_effect(key, 0, (stacks, mt), n_addrs=5)
    gs, gmt, _ = rc.get_effect(key, 0)
    assert np.array_equal(gs, stacks) and gmt == mt


def test_effect_record_idempotent_and_quarantine(estore):
    key = "12" * 16
    stacks, mt = _an_effect(2)
    rc.put_effect(key, 0, (stacks, mt), n_addrs=9)
    p = os.path.join(estore, f"{key}.e00000.npz")
    mtime = os.path.getmtime(p)
    rc.put_effect(key, 0, (stacks * 0, 0), n_addrs=1)  # same key+idx: kept
    assert os.path.getmtime(p) == mtime
    gs, _, _ = rc.get_effect(key, 0)
    assert np.array_equal(gs, stacks)
    # flip bytes: the checksum catches it, the record is quarantined
    with open(p, "r+b") as f:
        f.seek(60)
        f.write(b"\xff\xff\xff\xff")
    assert rc.get_effect(key, 0) is None
    assert not os.path.exists(p)


def test_gc_collects_orphaned_effects(estore):
    """Effects whose key has no chunk records are pre-v3-style orphans
    for gc; effects alongside live chunk records survive."""
    orphan, live = "aa" * 16, "bb" * 16
    stacks, mt = _an_effect(3)
    rc.put_effect(orphan, 0, (stacks, mt), n_addrs=2)
    rc.put_effect(live, 0, (stacks, mt), n_addrs=2)
    # a minimal chunk record under the live key
    np.savez(os.path.join(estore, f"{live}.c00000.npz"),
             marker=np.zeros(1))
    rep = rc.gc()
    assert not os.path.exists(os.path.join(estore,
                                           f"{orphan}.e00000.npz"))
    assert os.path.exists(os.path.join(estore, f"{live}.e00000.npz"))
    assert rep["orphans_removed"] >= 1


def test_reshard_composes_stored_effects(small_chunks):
    """The tentpole: a re-shard whose chunk records are gone but whose
    effect records survive preloads every chunk's incoming state from
    the store (effect hits observed) and stays bit-identical."""
    import glob

    n = 6 * 512
    stages = _paper_pipeline(n, seed=21)
    ref = simulate_dataflow_many(_paper_pipeline(n, seed=21),
                                 {"A": acp_cache()}, n,
                                 use_rescache=False)
    rc.clear()
    r1 = simulate_dataflow_many(stages, {"A": acp_cache()}, n, workers=2)
    c1 = rc.census()
    assert c1["effects"]["count"] > 0
    for p in glob.glob(os.path.join(small_chunks, "*.c*.npz")):
        os.unlink(p)
    rc.clear()
    rc.configure(enabled=True, directory=small_chunks)
    r2 = simulate_dataflow_many(_paper_pipeline(n, seed=21),
                                {"A": acp_cache()}, n, workers=2)
    c2 = rc.census()
    assert c2["effects"]["hits"] > 0, "master did not preload effects"
    k = ("A", 8)
    assert ref[k].cycles == r1[k].cycles == r2[k].cycles
    assert (ref[k].cache_hits, ref[k].cache_misses) == \
        (r2[k].cache_hits, r2[k].cache_misses)
