"""Public jit'd wrappers for the Pallas kernels.

Dispatch policy: on CPU (tests and development) the kernels execute under
``interpret=True`` (the kernel body is evaluated in Python/XLA-CPU —
bit-accurate semantics, no TPU required); on any other backend they are
compiled natively, so a backend the TPU kernels cannot target fails
instead of quietly interpreting.
Wrappers also handle padding to hardware-aligned block shapes and GQA
head-group plumbing so models never see alignment constraints.
"""

from __future__ import annotations


import math

import jax
import jax.numpy as jnp

from . import dataflow_matmul as _mm
from . import flash_attention as _fa
from . import grouped_matmul as _gmm
from . import mla_decode as _mla
from . import rmsnorm as _rn
from . import spmv as _spmv
from . import ref as ref  # re-exported for tests/benchmarks


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x: jax.Array, mult: int, axis: int) -> tuple[jax.Array, int]:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def matmul(x: jax.Array, w: jax.Array, *,
           block_m: int = 128, block_n: int = 128, block_k: int = 512,
           out_dtype: jnp.dtype | None = None) -> jax.Array:
    """Padded, decoupled-pipeline matmul; accepts any (M, K) × (K, N)."""
    M, K = x.shape
    _, N = w.shape
    bm = min(block_m, _ceil_mult(M, 8))
    bn = min(block_n, _ceil_mult(N, 128))
    bk = min(block_k, _ceil_mult(K, 128))
    xp, _ = _pad_to(x, bm, 0)
    xp, _ = _pad_to(xp, bk, 1)
    wp, _ = _pad_to(w, bk, 0)
    wp, _ = _pad_to(wp, bn, 1)
    out = _mm.dataflow_matmul(xp, wp, block_m=bm, block_n=bn, block_k=bk,
                              out_dtype=out_dtype, interpret=_interpret())
    return out[:M, :N]


def _ceil_mult(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: float | None = None,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """(B, Hq, Sq, d) × (B, Hkv, Sk, d)² → (B, Hq, Sq, d), GQA-aware."""
    B, Hq, Sq, d = q.shape
    Sk = k.shape[2]
    bq = min(block_q, _ceil_mult(Sq, 8))
    bk = min(block_k, _ceil_mult(Sk, 8))
    qp, _ = _pad_to(q, bq, 2)
    kp, _ = _pad_to(k, bk, 2)
    vp, _ = _pad_to(v, bk, 2)
    if not causal and kp.shape[2] != Sk:
        raise ValueError("non-causal padding unsupported; pad upstream")
    # padded queries attend causally to real keys only (pad rows discarded);
    # padded keys sit in the causal future of every real query.
    out = _fa.flash_attention(qp, kp, vp, causal=causal, scale=scale,
                              block_q=bq, block_k=bk,
                              interpret=_interpret())
    return out[:, :, :Sq, :]


def decode_attention(q, k_cache, v_cache, lengths, *,
                     scale: float | None = None,
                     block_s: int = 256) -> jax.Array:
    """(B, Hq, d) against (B, Hkv, S, d) caches with ragged lengths."""
    S = k_cache.shape[2]
    bs = min(block_s, _ceil_mult(S, 8))
    kp, _ = _pad_to(k_cache, bs, 2)
    vp, _ = _pad_to(v_cache, bs, 2)
    return _fa.decode_attention(q, kp, vp, lengths, scale=scale,
                                block_s=bs, interpret=_interpret())


def rmsnorm(x: jax.Array, weight: jax.Array, *, eps: float = 1e-6,
            block_rows: int = 256) -> jax.Array:
    """RMSNorm over the last axis; any leading shape."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    R = x2.shape[0]
    br = min(block_rows, R) if R % min(block_rows, R) == 0 else 1
    # choose the largest divisor of R that is <= block_rows
    br = max(b for b in range(1, min(block_rows, R) + 1) if R % b == 0)
    out = _rn.rmsnorm(x2, weight, eps=eps, block_rows=br,
                      interpret=_interpret())
    return out.reshape(shape)


def spmv(values, col_ids, x) -> jax.Array:
    """BSR SpMV (see kernels/spmv.py for the layout)."""
    return _spmv.spmv_bsr(values, col_ids, x, interpret=_interpret())


csr_to_bsr = _spmv.csr_to_bsr


def grouped_block_rows(rows: int, groups: int) -> int:
    """Row tile of a grouped matmul over ``rows`` rows that ``groups``
    groups share: twice a group's mean share, as a power of two from 16
    (bf16's sublane tile) to 512.  A decode step's dozen rows an expert
    take one tile of 32; a 4,096-token prefill block's hundreds, tiles of
    512."""
    return min(512, max(16, 1 << math.ceil(math.log2(2 * rows / groups))))


def grouped_rows(rows: int, groups: int, block_rows: int) -> int:
    """Rows of the tile-aligned layout of ``rows`` rows in ``groups``
    groups: every group's padding to whole tiles counted."""
    return _gmm.num_tiles(rows, groups, block_rows) * block_rows


group_tiles = _gmm.group_tiles

#: VMEM for one call's weight blocks, double buffered
_GMM_WEIGHT_VMEM = 24 * 1024 * 1024


def grouped_block_cols(K: int, N: int, weights: int, itemsize: int) -> int:
    """Column tile of a grouped matmul of K rows of weights by N columns,
    ``weights`` such matrices a call: the widest multiple of 128 lanes
    dividing N whose blocks fit :data:`_GMM_WEIGHT_VMEM` double buffered
    (N whole if it is not a multiple of 128)."""
    if N % 128:
        return N
    lanes = N // 128
    fits = [c for c in range(1, lanes + 1) if lanes % c == 0 and
            2 * weights * K * 128 * c * itemsize <= _GMM_WEIGHT_VMEM]
    return 128 * max(fits, default=1)


def grouped_matmul(x: jax.Array, w: jax.Array, tiles: jax.Array,
                   layer: jax.Array, *, block_rows: int,
                   w_up: jax.Array | None = None) -> jax.Array:
    """Grouped matmul of tile-aligned rows ``x`` against groups
    ``layer · groups`` onwards of the stacked ``w`` (see
    kernels/grouped_matmul.py); ``tiles`` from :func:`group_tiles`.  K is
    whole, N in tiles of :func:`grouped_block_cols`."""
    K, N = w.shape[1:]
    block_cols = grouped_block_cols(K, N, 1 if w_up is None else 2,
                                    w.dtype.itemsize)
    return _gmm.grouped_matmul(x, w, tiles, layer, w_up,
                               groups=tiles.shape[0] - 1,
                               block_rows=block_rows, block_cols=block_cols,
                               interpret=_interpret())


#: positions in a block of the MLA decode kernel
MLA_BLOCK = _mla.BLOCK

#: VMEM for one MLA decode call's blocks and row state
_MLA_VMEM = 24 * 1024 * 1024


def mla_decode_block_rows(B: int, H: int, r: int, rope: int,
                          itemsize: int) -> int:
    """Batch rows a tile of the MLA decode kernel: the largest power of
    two dividing B whose tile fits :data:`_MLA_VMEM` with its cache blocks
    and queries double buffered, and its output (double buffered) and
    accumulator in float32."""
    row = (2 * (MLA_BLOCK + H) * (r + rope) * itemsize + 3 * H * r * 4)
    rows = B & -B
    while rows > 1 and rows * row > _MLA_VMEM:
        rows //= 2
    return rows


def mla_decode(q_lat: jax.Array, q_pe: jax.Array, c_kv: jax.Array,
               k_pe: jax.Array, rep: jax.Array, length: jax.Array, *,
               scale: float) -> jax.Array:
    """Absorbed-MLA decode attention over layer ``rep`` of the stacked
    latent cache (see kernels/mla_decode.py): ``q_lat`` (B, H, r) and
    ``q_pe`` (B, H, rope) against ``c_kv`` (repeats, B, S, r) and ``k_pe``
    (repeats, B, rope, S'), positions ``0 .. length``; ``o_lat`` (B, H, r)
    float32.  The queries enter the products in the cache's dtype."""
    B, H, r = q_lat.shape
    rows = mla_decode_block_rows(B, H, r, q_pe.shape[-1],
                                 c_kv.dtype.itemsize)
    return _mla.mla_decode(q_lat.astype(c_kv.dtype),
                           q_pe.astype(k_pe.dtype), c_kv, k_pe, rep, length,
                           scale=scale, block_rows=rows,
                           interpret=_interpret())
