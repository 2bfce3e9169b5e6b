"""jit'd dispatch layer: Pallas kernels on TPU, jnp references elsewhere.

The model code calls these entry points; on a TPU backend they route to
the Pallas kernels, on any other backend to ``ref.py`` (which the dry-run
lowers).  Off-TPU, ``REPRO_FORCE_INTERPRET=1`` forces the Pallas path in
interpret mode (used by the kernel integration tests); a TPU backend
ignores it, so a stray variable can never run the chip in interpret mode.
"""
from __future__ import annotations

import os

import jax

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _pl_decode
from repro.kernels.diffusive_phi import diffusive_phi as _pl_phi
from repro.kernels.diffusive_phi import \
    diffusive_phi_sparse as _pl_phi_sparse
from repro.kernels.flash_attention import flash_attention as _pl_flash
from repro.kernels.mamba_scan import mamba_scan as _pl_mamba
from repro.kernels.rglru_scan import rglru_scan as _pl_rglru
from repro.kernels.rmsnorm import rmsnorm as _pl_rmsnorm


def _mode() -> str:
    if jax.default_backend() == "tpu":
        return "tpu"
    if os.environ.get("REPRO_FORCE_INTERPRET") == "1":
        return "interpret"
    return "ref"


def flash_attention(q, k, v, *, causal=True, window=0):
    m = _mode()
    if m == "ref":
        return ref.flash_attention(q, k, v, causal=causal, window=window)
    return _pl_flash(q, k, v, causal=causal, window=window,
                     interpret=(m == "interpret"))


def decode_attention(q, k, v, pos, *, window=0):
    m = _mode()
    if m == "ref":
        return ref.decode_attention(q, k, v, pos, window=window)
    return _pl_decode(q, k, v, pos, window=window,
                      interpret=(m == "interpret"))


def diffusive_phi(inv_phi, F, d_tx_masked):
    m = _mode()
    if m == "ref":
        return ref.diffusive_phi(inv_phi, F, d_tx_masked)
    return _pl_phi(inv_phi, F, d_tx_masked, interpret=(m == "interpret"))


def diffusive_phi_sparse(inv_phi, F, d_tx_masked, nbr):
    m = _mode()
    if m == "ref":
        return ref.diffusive_phi_sparse(inv_phi, F, d_tx_masked, nbr)
    return _pl_phi_sparse(inv_phi, F, d_tx_masked, nbr,
                          interpret=(m == "interpret"))


def rglru_scan(a, b):
    m = _mode()
    if m == "ref":
        return ref.rglru_scan(a, b)
    return _pl_rglru(a, b, interpret=(m == "interpret"))


def mamba_scan(a, b, C):
    m = _mode()
    if m == "ref":
        return ref.mamba_scan(a, b, C)
    return _pl_mamba(a, b, C, interpret=(m == "interpret"))


def rmsnorm(x, scale, eps=1e-6):
    m = _mode()
    if m == "ref":
        return ref.rmsnorm(x, scale, eps)
    return _pl_rmsnorm(x, scale, eps=eps, interpret=(m == "interpret"))
