"""The dataflow reference, solved in blocks of the FIFO depth, against
the same recurrence stepped one iteration and one stage at a time in
plain Python, on small pipelines where FIFOs fill, caches evict, stages
sit on their dependence cycle and accesses skip iterations."""

from __future__ import annotations

import copy
import os

import numpy as np
import pytest

from benchtest import BENCH

import harness


@pytest.fixture(scope="module")
def ref():
    return harness.load_module(os.path.join(BENCH, "configs",
                                            "spmv-table1.py"),
                               "bench_spmv_ref_test")


@pytest.fixture(scope="module")
def base():
    return harness.load_json(BENCH, "configs", "spmv-table1.json")


def scalar_dataflow(ref, cfg: dict, memory: str, seed: int,
                    control: str | None = None,
                    chunk: int = 1 << 16) -> dict:
    """The dataflow template on ``cfg["pipeline"]``: stage ``s`` starts
    iteration ``i`` at the latest of its own previous start plus its
    increment, its producer's finish of ``i``, and the start of
    iteration ``i - depth`` downstream (a full FIFO)."""
    mem = cfg["memory_models"][memory]
    n = cfg["iterations"]
    depth = cfg["fifo_depth"]
    pipeline = cfg["pipeline"]
    gens = ref.traces(cfg, seed)
    S = len(pipeline)
    cache_cfg = mem.get("cache")
    cache = ref.LRUCache(cache_cfg["size_bytes"], cache_cfg["line_bytes"],
                     cache_cfg["ways"],
                     refresh_on_hit=control != "fifo_replacement") \
        if cache_cfg else None
    line_bytes = cache_cfg["line_bytes"] if cache_cfg else 32
    hit_cycles = cache_cfg["hit_cycles"] if cache_cfg else 0
    backing = ref._Backing(mem, seed)
    mo = mem["max_outstanding"]
    burst_cycles = int(np.ceil(1.0 / mem["words_per_cycle"]))
    backpressure = control != "no_backpressure"

    regions = [st["regions"] for st in pipeline]
    ii = [st["ii"] for st in pipeline]
    lat = [st["latency"] for st in pipeline]
    in_scc = [st["mem_in_scc"] for st in pipeline]
    # start times of the last `depth` iterations of each stage (ring)
    ring = [[0] * depth for _ in range(S)]
    prev_start = [0] * S
    prev_addr = [[-1] * len(r) for r in regions]
    stalls = [{"ii": 0, "upstream": 0, "fifo": 0, "memory": 0}
              for _ in range(S)]
    finish_last = 0
    for lo in range(0, n, chunk):
        hi = min(n, lo + chunk)
        addrs = {name: g(lo, hi).tolist() for name, g in gens.items()}
        for j in range(hi - lo):
            i = lo + j
            slot = i % depth
            finish_up = 0
            for s in range(S):
                al_sp = 0
                al_sum = 0
                burst = 0
                for k, region in enumerate(regions[s]):
                    a = addrs[region][j]
                    p = prev_addr[s][k]
                    prev_addr[s][k] = a
                    if a < 0:
                        continue
                    if not in_scc[s] and p >= 0 and abs(a - p) <= line_bytes:
                        burst += 1
                        continue
                    if cache is not None and cache.access(a):
                        al = hit_cycles
                    else:
                        al = backing.latency()
                    al_sum += al
                    if al > al_sp:
                        al_sp = al
                if in_scc[s]:
                    c = ii[s] + al_sum
                    fin = lat[s]
                else:
                    c = max(ii[s], -(-al_sp // mo), burst * burst_cycles)
                    fin = lat[s] + al_sp
                t_self = prev_start[s] + c if i > 0 else 0
                t_up = finish_up if s > 0 else 0
                t_bp = ring[s + 1][slot] \
                    if backpressure and s + 1 < S and i >= depth else 0
                t = max(t_self, t_up, t_bp)
                b = stalls[s]
                if i > 0:
                    b["ii"] += ii[s] - 1
                    b["memory"] += c - ii[s]
                if t > t_self:
                    b["fifo" if t_bp > t_up else "upstream"] += t - t_self
                prev_start[s] = t
                ring[s][slot] = t
                finish_up = t + fin
            finish_last = finish_up
    out = {"cycles": finish_last,
           "cache_hits": cache.hits if cache else 0,
           "cache_misses": cache.misses if cache else 0}
    for st, b in zip(pipeline, stalls):
        for bucket, v in b.items():
            out[f"stall.{st['name']}.{bucket}"] = v
    return out


def _case(base, depth, cache_bytes, in_scc, n, wpc):
    cfg = copy.deepcopy(base)
    cfg.update(iterations=n, fifo_depth=depth, dim=512)
    mem = cfg["memory_models"]["ACP+64KB"]
    mem["cache"]["size_bytes"] = cache_bytes
    mem["words_per_cycle"] = wpc
    cfg["pipeline"][2]["mem_in_scc"] = in_scc
    return cfg


@pytest.mark.parametrize("depth,cache_bytes,in_scc,n,wpc", [
    (256, 65536, False, 5000, 1.0),
    (3, 256, False, 3001, 1.0),
    (1, 128, True, 2000, 0.5),
    (7, 512, False, 4099, 0.25),
    (16, 1024, True, 3333, 1.0),
])
@pytest.mark.parametrize("control", [None, "no_backpressure",
                                     "fifo_replacement"])
def test_blocked_equals_scalar(ref, base, depth, cache_bytes, in_scc, n,
                               wpc, control):
    cfg = _case(base, depth, cache_bytes, in_scc, n, wpc)
    seed = 3_100_000_000 + depth
    want = scalar_dataflow(ref, cfg, "ACP+64KB", seed, control, chunk=1000)
    got = ref.simulate_dataflow(cfg, "ACP+64KB", seed, control, chunk=777)
    assert got == want


def test_skipped_accesses_break_bursts(ref, base, monkeypatch):
    """An iteration with no access (address -1) issues nothing, and the
    access after it is no burst continuation."""
    cfg = _case(base, 4, 256, False, 3000, 1.0)
    real = ref.traces

    def holes(cfg, seed):
        gens = real(cfg, seed)
        cols = gens["cols"]
        gens["cols"] = lambda lo, hi: np.where(
            np.arange(lo, hi) % 5 == 2, -1, cols(lo, hi))
        return gens
    monkeypatch.setattr(ref, "traces", holes)
    want = scalar_dataflow(ref, cfg, "ACP+64KB", 9, chunk=1000)
    assert ref.simulate_dataflow(cfg, "ACP+64KB", 9, chunk=512) == want
    assert want["stall.s0.memory"] > 0
