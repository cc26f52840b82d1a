"""Jit'd public wrappers around the Pallas kernels.

``interpret_mode`` is the one place the Pallas execution mode is decided:
compiled on a TPU, interpreted on any other platform.  The wrappers take
no ``interpret`` argument, so no caller can run a kernel interpreted on a
TPU; the serving engine prints the mode once, so a run always says how
its kernels executed.  The EASEY
AutoTuner picks kernel vs reference ops per target (plan.kernels), which
is the paper's `###includelocalmpi###` mechanism applied to compute
libraries.
"""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mla_attention import mla_decode_attention_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.sedov_stencil import cfl_dt, sedov_step_pallas


def interpret_mode() -> bool:
    """True (Pallas interpreter) unless JAX's default backend is a TPU,
    which always runs the kernels compiled."""
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "kv_len"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, kv_len: int | None = None):
    return flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k, kv_len=kv_len,
                                  interpret=interpret_mode())


@jax.jit
def paged_attention(q, k_pages, v_pages, page_table, kv_len, layer=None):
    """Fused paged decode attention (see kernels/paged_attention.py).

    q: (slots, H, dh); k_pages/v_pages: every layer's pool
    (layers, num_pages, page_size, rows, lanes) read at int32 ``layer``,
    or one layer's (num_pages, page_size, rows, lanes) with no ``layer``,
    a token's K heads as (K, dh) or packed to 128-lane rows
    (``serving/pool.page_rows``);
    page_table: (slots, max_pages) int32; kv_len: (slots,) int32.
    """
    return paged_attention_pallas(q, k_pages, v_pages, page_table, kv_len,
                                  layer, interpret=interpret_mode())


@partial(jax.jit, static_argnames=("eps", "block_rows"))
def rmsnorm(x, w, eps: float = 1e-6, block_rows: int = 256):
    return rmsnorm_pallas(x, w, eps=eps, block_rows=block_rows,
                          interpret=interpret_mode())


def sedov_step_kernel(state: dict, cfg, block_x: int = 16) -> dict:
    """Fused LULESH step: global CFL reduction + Pallas stencil update."""
    dt = cfl_dt(state)
    return sedov_step_pallas(state, dt, block_x=block_x,
                             interpret=interpret_mode())


@partial(jax.jit, static_argnames=("scale", "value_lanes"))
def mla_decode_attention(q, pool, page_table, kv_len, layer, *, scale: float,
                         value_lanes: int):
    """Paged latent-attention decode (see kernels/mla_attention.py).

    q: (slots, H, lanes) absorbed queries; pool: (layers, num_pages,
    page_size, lanes) read at int32 ``layer``; page_table: (slots,
    max_pages) int32; kv_len: (slots,) int32 (0: nothing to attend).
    Returns (slots, H, value_lanes)."""
    return mla_decode_attention_pallas(
        q, pool, page_table, kv_len, layer, scale=scale,
        value_lanes=value_lanes, interpret=interpret_mode())
