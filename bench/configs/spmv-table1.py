"""Table-I spmv: its workload generator and its plain reference.

The workload is the paper's sparse matrix-vector product (a 4096 x 4096
CSR matrix at density 0.25, so 4,194,304 inner-loop iterations).  Its
loop body is what the compiler partitions; its byte-address traces are
what the simulator's memory models see.  Both are made here from the
seed, so the yardstick does not move with the program.

The plain reference simulates the configured pipeline one iteration at a
time in plain Python: a list-based LRU per cache, one backing-store draw
per request that reaches past the cache, and the start/finish recurrence
with bounded FIFOs.  It imports nothing of the program.  ``control``
breaks one guarantee the configuration states, for the check that the
comparison can fail:

* ``"no_backpressure"``: the FIFOs are treated as unbounded;
* ``"fifo_replacement"``: the caches stop refreshing a line on a hit.
"""

from __future__ import annotations

import numpy as np

MASK64 = (1 << 64) - 1

#: the controls each machine model can have
CONTROLS = {"dataflow": ("no_backpressure", "fifo_replacement"),
            "processor": ("fifo_replacement",)}


# ---------------------------------------------------------------------------
# Workload: loop body and traces (the paper kernel's generator, scale 1.0)
# ---------------------------------------------------------------------------

def _mix64(x: np.ndarray) -> np.ndarray:
    """SplitMix64 finalizer over uint64."""
    x = x + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def hash_ints(lo: int, hi: int, bound: int, salt: int) -> np.ndarray:
    """Uniform ints in [0, bound) for iterations [lo, hi)."""
    idx = np.arange(lo, hi, dtype=np.uint64)
    salt_mul = np.uint64((salt * 0xD1342543DE82EF95) & MASK64)
    with np.errstate(over="ignore"):
        h = _mix64(idx + salt_mul)
    return (h % np.uint64(bound)).astype(np.int64)


def traces(cfg: dict, seed: int) -> dict:
    """Region name -> ``gen(lo, hi)`` of byte addresses, for all
    ``cfg["iterations"]`` iterations: the column indices and values
    stream, the gathered vector entry is a hash of the iteration."""
    dim = cfg["dim"]
    return {
        "cols": lambda lo, hi: np.arange(lo, hi, dtype=np.int64) * 4,
        "vals": lambda lo, hi: np.arange(lo, hi, dtype=np.int64) * 4
        + (1 << 24),
        "x": lambda lo, hi: hash_ints(lo, hi, dim, seed + 100) * 4
        + (1 << 25),
    }


def loop_kernel(cfg: dict, seed: int):
    """``(loop_body, carry_example, body_args)`` of one CSR inner-loop
    iteration over a random matrix of the configured size."""
    import jax.numpy as jnp

    dim, density = cfg["dim"], cfg["density"]
    rng = np.random.default_rng(seed)
    nnz_per_row = np.maximum(1, rng.binomial(dim, density, size=dim))
    indices = np.concatenate([
        np.sort(rng.choice(dim, size=n, replace=False))
        for n in nnz_per_row]).astype(np.int32)
    data = rng.normal(size=len(indices)).astype(np.float32)
    x = rng.normal(size=dim).astype(np.float32)
    vals_j, cols_j, x_j = jnp.asarray(data), jnp.asarray(indices), \
        jnp.asarray(x)

    def loop_body(acc, j, vals=vals_j, cols=cols_j, xv=x_j):
        c = cols[j]          # sequential index load
        v = vals[j]          # sequential value load
        xx = xv[c]           # data-dependent gather
        return acc + v * xx  # multiply feeding the accumulation cycle

    return loop_body, jnp.float32(0.0), (jnp.int32(0),)


# ---------------------------------------------------------------------------
# Plain reference
# ---------------------------------------------------------------------------

class LRUCache:
    """Set-associative cache over byte addresses; each set is a list of
    tags, least recently used first."""

    def __init__(self, size_bytes: int, line_bytes: int, ways: int,
                 refresh_on_hit: bool = True):
        self.line_bytes = line_bytes
        self.ways = ways
        self.n_sets = max(1, size_bytes // line_bytes // ways)
        self.sets = [[] for _ in range(self.n_sets)]
        self.refresh_on_hit = refresh_on_hit
        self.hits = 0
        self.misses = 0

    def access(self, addr: int) -> bool:
        line = addr // self.line_bytes
        tags = self.sets[line % self.n_sets]
        tag = line // self.n_sets
        if tag in tags:
            if self.refresh_on_hit:
                tags.remove(tag)
                tags.append(tag)
            self.hits += 1
            return True
        if len(tags) == self.ways:
            del tags[0]
        tags.append(tag)
        self.misses += 1
        return False


class _Backing:
    """Backing-store trips: one uniform draw per request, in order."""

    def __init__(self, mem: dict, seed: int):
        self.rng = np.random.default_rng(seed)
        self.rate = mem["backing_hit_rate"]
        self.port = mem["port_latency"]
        self.dram = mem["dram_latency"]
        self.buf: list[float] = []

    def latency(self) -> int:
        if self.rate <= 0.0:
            return self.dram
        if not self.buf:
            self.buf = self.rng.random(1 << 16).tolist()[::-1]
        return self.port if self.buf.pop() < self.rate else self.dram


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _memory_latencies(mem: dict, addrs: list[int], cache, backing,
                      hit_cycles: int) -> list[int]:
    """Latency of each request, in order: a cache hit, or one trip to the
    backing store."""
    out = []
    for a in addrs:
        if cache is not None and cache.access(a):
            out.append(hit_cycles)
        else:
            out.append(backing.latency())
    return out


def simulate_dataflow(cfg: dict, memory: str, seed: int,
                      control: str | None = None,
                      chunk: int = 1 << 16) -> dict:
    """The dataflow template on ``cfg["pipeline"]``.  Stage ``s`` starts
    iteration ``i`` at the latest of its own previous start plus its
    increment ``c``, its producer's finish of ``i``, and the start of
    iteration ``i - depth`` downstream (a full FIFO).  Requests go to the
    memory system one at a time in iteration order, then stage order; an
    access within one line of the same access's previous address streams
    as a burst and issues no request (unless the stage's access sits on
    its dependence cycle).

    The recurrence is solved in blocks of ``depth`` iterations: within a
    block the FIFO term only reads the block before, so each stage's
    starts are a running maximum, ``start = C + max.accumulate(bound -
    C)`` over the block's prefix sums ``C`` of ``c``."""
    mem = cfg["memory_models"][memory]
    n = cfg["iterations"]
    depth = cfg["fifo_depth"]
    pipeline = cfg["pipeline"]
    gens = traces(cfg, seed)
    S = len(pipeline)
    cache_cfg = mem.get("cache")
    cache = LRUCache(cache_cfg["size_bytes"], cache_cfg["line_bytes"],
                     cache_cfg["ways"],
                     refresh_on_hit=control != "fifo_replacement") \
        if cache_cfg else None
    line_bytes = cache_cfg["line_bytes"] if cache_cfg else 32
    hit_cycles = cache_cfg["hit_cycles"] if cache_cfg else 0
    backing = _Backing(mem, seed)
    mo = mem["max_outstanding"]
    burst_cycles = int(np.ceil(1.0 / mem["words_per_cycle"]))
    backpressure = control != "no_backpressure"

    cols = [(s, r) for s, st in enumerate(pipeline) for r in st["regions"]]
    in_scc = np.array([st["mem_in_scc"] for st in pipeline])
    ii = np.array([st["ii"] for st in pipeline], np.int64)
    lat = np.array([st["latency"] for st in pipeline], np.int64)
    stalls = np.zeros((S, 4), np.int64)        # ii, upstream, fifo, memory
    prev_addr = {col: -1 for col in cols}
    last_start = np.zeros(S, np.int64)          # start of iteration i - 1
    tail = np.zeros((S, 0), np.int64)           # starts of the last block
    finish_last = 0
    for lo in range(0, n, chunk):
        hi = min(n, lo + chunk)
        m = hi - lo
        A = np.stack([gens[r](lo, hi) for _, r in cols], axis=1)
        P = np.empty_like(A)
        P[0] = [prev_addr[col] for col in cols]
        P[1:] = A[:-1]
        prev_addr = {col: int(A[-1, k]) for k, col in enumerate(cols)}
        valid = A >= 0
        burst = valid & (P >= 0) & (np.abs(A - P) <= line_bytes) \
            & ~in_scc[[s for s, _ in cols]]
        req = valid & ~burst
        L = np.zeros(A.shape, np.int64)
        L[req] = _memory_latencies(mem, A[req].tolist(), cache, backing,
                                   hit_cycles)     # row-major: in order
        c = np.empty((S, m), np.int64)
        fin = np.empty((S, m), np.int64)
        for s in range(S):
            ks = [k for k, (t, _) in enumerate(cols) if t == s]
            al_sp = L[:, ks].max(axis=1) if ks else np.zeros(m, np.int64)
            if in_scc[s]:
                c[s] = ii[s] + (L[:, ks].sum(axis=1) if ks else 0)
                fin[s] = lat[s]
            else:
                nb = burst[:, ks].sum(axis=1) if ks else 0
                c[s] = np.maximum(np.maximum(ii[s], -(-al_sp // mo)),
                                  nb * burst_cycles)
                fin[s] = lat[s] + al_sp
        if lo == 0:
            c[:, 0] = 0      # iteration 0 starts on no previous start
            stalls[:, 0] -= ii - 1
            stalls[:, 3] -= 0 - ii
        stalls[:, 0] += (ii - 1) * m
        stalls[:, 3] += (c - ii[:, None]).sum(axis=1)
        for b0 in range(0, m, depth):
            b1 = min(m, b0 + depth)
            start = np.empty((S, b1 - b0), np.int64)
            up = np.zeros(b1 - b0, np.int64)
            for s in range(S):
                bp = np.zeros(b1 - b0, np.int64)
                if backpressure and s + 1 < S:
                    # the starts of iterations i - depth downstream, all
                    # in the last `depth` starts (blocks are no longer)
                    idx = np.arange(b1 - b0) + tail.shape[1] - depth
                    ok = idx >= 0
                    bp[ok] = tail[s + 1, idx[ok]]
                bound = np.maximum(up, bp)
                C = np.cumsum(c[s, b0:b1])
                st = C + np.maximum.accumulate(
                    np.maximum(bound - C, last_start[s]))
                t_self = np.concatenate([[last_start[s]], st[:-1]]) \
                    + c[s, b0:b1]
                gap = st - t_self
                fifo = bp > up
                stalls[s, 2] += gap[fifo].sum()
                stalls[s, 1] += gap[~fifo].sum()
                start[s] = st
                last_start[s] = st[-1]
                up = st + fin[s, b0:b1]
            tail = start if b1 - b0 == depth else \
                np.concatenate([tail, start], axis=1)[:, -depth:]
            finish_last = int(up[-1])
    out = {"cycles": finish_last,
           "cache_hits": cache.hits if cache else 0,
           "cache_misses": cache.misses if cache else 0}
    for st, row in zip(pipeline, stalls):
        for bucket, v in zip(("ii", "upstream", "fifo", "memory"), row):
            out[f"stall.{st['name']}.{bucket}"] = int(v)
    return out


def simulate_processor(cfg: dict, seed: int, control: str | None = None,
                       chunk: int = 1 << 16) -> dict:
    """The processor baseline: IPC-bound issue plus an L1/L2 hierarchy
    walked in iteration order, half of each DRAM trip overlapped."""
    p = cfg["processor"]
    n = cfg["iterations"]
    refresh = control != "fifo_replacement"
    l1 = LRUCache(p["l1_kb"] * 1024, p["line_bytes"], p["l1_ways"], refresh)
    l2 = LRUCache(p["l2_kb"] * 1024, p["line_bytes"], p["l2_ways"], refresh)
    gens = traces(cfg, seed)
    order = p["access_order"]
    l2_hits = dram = 0
    for lo in range(0, n, chunk):
        hi = min(n, lo + chunk)
        cols = [gens[name](lo, hi).tolist() for name in order]
        for row in zip(*cols):
            for a in row:
                if a < 0 or l1.access(a):
                    continue
                if l2.access(a):
                    l2_hits += 1
                else:
                    dram += 1
    cycles = (n * p["instrs_per_iter"] / p["ipc"]
              + l2_hits * p["l2_hit"] + dram * p["dram"] * 0.5)
    return {"cycles": int(cycles),
            "stall.core.memory": int(l2_hits * p["l2_hit"]
                                     + dram * p["dram"] * 0.5),
            "cache_hits": l1.hits + l2.hits,
            "cache_misses": l2.misses}
