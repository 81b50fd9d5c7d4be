"""Pallas TPU kernels for the compute hot-spots, with pure-jnp oracles.

Each kernel follows the package contract: <name>.py holds the
``pl.pallas_call`` + BlockSpec implementation, ``ops.py`` the jit'd public
wrapper (padding, GQA plumbing, interpret mode on CPU), ``ref.py`` the
pure-jnp oracle used by the allclose test sweeps.
"""

from .ops import (matmul, flash_attention, decode_attention, rmsnorm, spmv,
                  csr_to_bsr)
from .decoupled_gather import (decoupled_gather, decoupled_gather_ref,
                               decoupled_gather_staged)
from . import ref

__all__ = ["matmul", "flash_attention", "decode_attention", "rmsnorm",
           "spmv", "csr_to_bsr", "decoupled_gather",
           "decoupled_gather_ref", "decoupled_gather_staged", "ref"]
