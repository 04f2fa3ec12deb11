"""Jit'd public wrappers around the Pallas kernels.

Responsibilities:

* **Padding** to MXU/block-aligned shapes (head_dim -> multiple of 128,
  sequence -> block multiples, GMM dims -> tile multiples) and un-padding
  the result.  Zero/masked padding is exact for all three kernels.
* **Backend dispatch**: on TPU the kernels compile natively; on a CPU
  (tests) they run under ``interpret=True``, which executes the kernel body
  through XLA — bit-for-bit the same program, minus the hardware.  The detection lives in :mod:`repro.kernels.backend` (shared by
  every kernel module, including the router-step kernel).
* **Autodiff**: Pallas calls have no automatic VJP.  Each op carries a
  ``jax.custom_vjp`` whose backward pass recomputes through the pure-jnp
  reference (flash/SSD) or through two more grouped matmuls (GMM, exact) —
  the standard fwd-kernel + recompute-bwd production compromise.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref as _ref
from .backend import use_interpret
from .flash_attention import flash_attention as _flash_pallas
from .moe_gmm import grouped_matmul_pallas as _gmm_pallas
from .ssd_scan import ssd_scan as _ssd_pallas

__all__ = ["flash_attention_op", "ssd_scan_op", "grouped_matmul"]

# deprecated alias — the detection's canonical home is kernels.backend
_interpret = use_interpret


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_op(q, k, v, causal: bool = True,
                       window: Optional[int] = None,
                       block_q: int = 512, block_k: int = 512):
    """q: (B, S, H, hd); k/v: (B, S, K, hd) -> (B, S, H, hd)."""
    return _flash_fwd_impl(q, k, v, causal, window, block_q, block_k)


def _flash_fwd_impl(q, k, v, causal, window, block_q, block_k):
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    qt = _pad_to(q.transpose(0, 2, 1, 3), 3, 128)
    kt = _pad_to(k.transpose(0, 2, 1, 3), 3, 128)
    vt = _pad_to(v.transpose(0, 2, 1, 3), 3, 128)
    bq = min(block_q, max(16, 1 << (sq - 1).bit_length()))
    bk = min(block_k, max(16, 1 << (sk - 1).bit_length()))
    qt = _pad_to(qt, 2, bq)
    kt = _pad_to(kt, 2, bk)
    vt = _pad_to(vt, 2, bk)
    out = _flash_pallas(qt, kt, vt, causal=causal, window=window,
                        block_q=bq, block_k=bk, kv_len=sk,
                        sm_scale=hd ** -0.5,  # the UNpadded head_dim scale
                        interpret=_interpret())
    return out[:, :, :sq, :hd].transpose(0, 2, 1, 3)


def _flash_vjp_fwd(q, k, v, causal, window, block_q, block_k):
    return _flash_fwd_impl(q, k, v, causal, window, block_q, block_k), (q, k, v)


def _flash_vjp_bwd(causal, window, block_q, block_k, res, g):
    q, k, v = res

    def f(q_, k_, v_):
        out = _ref.flash_attention_ref(
            q_.transpose(0, 2, 1, 3), k_.transpose(0, 2, 1, 3),
            v_.transpose(0, 2, 1, 3), causal=causal, window=window)
        return out.transpose(0, 2, 1, 3)

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


flash_attention_op.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def ssd_scan_op(x, dt, B, C, A, chunk: int = 256):
    """x: (b, S, H, P); dt: (b, S, H); B/C: (b, S, G, N); A: (H,)."""
    return _ssd_fwd_impl(x, dt, B, C, A, chunk)


def _ssd_fwd_impl(x, dt, B, C, A, chunk):
    b, s, h, p = x.shape
    chunk = min(chunk, max(16, 1 << (s - 1).bit_length()))
    xt = _pad_to(x.transpose(0, 2, 1, 3), 2, chunk)
    dtt = _pad_to(dt.transpose(0, 2, 1), 2, chunk)   # dt=0 padding is exact
    Bt = _pad_to(B.transpose(0, 2, 1, 3), 2, chunk)
    Ct = _pad_to(C.transpose(0, 2, 1, 3), 2, chunk)
    y = _ssd_pallas(xt, dtt, Bt, Ct, A, chunk=chunk, interpret=_interpret())
    return y[:, :, :s].transpose(0, 2, 1, 3)


def _ssd_vjp_fwd(x, dt, B, C, A, chunk):
    return _ssd_fwd_impl(x, dt, B, C, A, chunk), (x, dt, B, C, A)


def _ssd_vjp_bwd(chunk, res, g):
    x, dt, B, C, A = res

    def f(x_, dt_, B_, C_, A_):
        y = _ref.ssd_scan_ref(x_.transpose(0, 2, 1, 3), dt_.transpose(0, 2, 1),
                              B_.transpose(0, 2, 1, 3), C_.transpose(0, 2, 1, 3),
                              A_)
        return y.transpose(0, 2, 1, 3)

    _, vjp = jax.vjp(f, x, dt, B, C, A)
    return vjp(g)


ssd_scan_op.defvjp(_ssd_vjp_fwd, _ssd_vjp_bwd)


# ---------------------------------------------------------------------------
# grouped matmul
# ---------------------------------------------------------------------------

def grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                   impl: Optional[str] = None) -> jax.Array:
    """(E, M, K) @ (E, K, N) -> (E, M, N).

    ``impl=None`` uses the XLA einsum (differentiable, fuses with
    neighbours); ``impl='pallas'`` uses the tiled kernel with an exact
    two-GMM backward.
    """
    if impl is None:
        return _ref.grouped_matmul_ref(lhs, rhs)
    if impl == "pallas":
        return _gmm_op(lhs, rhs)
    raise ValueError(f"unknown gmm impl {impl!r}")


@jax.custom_vjp
def _gmm_op(lhs, rhs):
    return _gmm_impl(lhs, rhs)


def _gmm_impl(lhs, rhs):
    e, m, k = lhs.shape
    n = rhs.shape[-1]
    bm = min(128, max(8, 1 << (m - 1).bit_length()))
    bn = min(128, max(128, 1 << (n - 1).bit_length())) if n >= 128 else 128
    bkk = min(512, max(128, 1 << (k - 1).bit_length())) if k >= 128 else 128
    lp = _pad_to(_pad_to(lhs, 1, bm), 2, bkk)
    rp = _pad_to(_pad_to(rhs, 1, bkk), 2, bn)
    out = _gmm_pallas(lp, rp, block_m=bm, block_n=bn, block_k=bkk,
                      interpret=_interpret())
    return out[:, :m, :n]


def _gmm_vjp_fwd(lhs, rhs):
    return _gmm_impl(lhs, rhs), (lhs, rhs)


def _gmm_vjp_bwd(res, g):
    lhs, rhs = res
    # d_lhs[e] = g[e] @ rhs[e]^T ; d_rhs[e] = lhs[e]^T @ g[e]  (exact)
    d_lhs = _gmm_impl(g, rhs.transpose(0, 2, 1)).astype(lhs.dtype)
    d_rhs = _gmm_impl(lhs.transpose(0, 2, 1), g).astype(rhs.dtype)
    return d_lhs, d_rhs


_gmm_op.defvjp(_gmm_vjp_fwd, _gmm_vjp_bwd)
