"""Backend-dispatching jit'd wrappers for the Pallas kernels.

``use_pallas='auto'`` selects the Pallas kernel on TPU and the pure-jnp
reference elsewhere (Pallas does not lower to the CPU host platform; the
dry-run therefore analyses the reference HLO — conservative for the paths
we hand-optimize). ``use_pallas=True`` demands the compiled kernel and
raises off a TPU; ``use_pallas='interpret'`` runs the kernel body in Python
— how the tests validate it. Nothing falls back to another path silently.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import grouped_matmul as _gmm
from repro.kernels import ssd_scan as _ssd
from repro.kernels import ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(use_pallas) -> Tuple[bool, bool]:
    """-> (use_kernel, interpret)."""
    if use_pallas == "auto":
        return (_on_tpu(), False)
    if use_pallas == "interpret":
        return (True, True)
    if use_pallas and not _on_tpu():
        raise RuntimeError(
            f"use_pallas=True needs a TPU backend, not "
            f"{jax.default_backend()!r}; ask for use_pallas='interpret' to "
            f"run the kernel body off the chip")
    return (bool(use_pallas), False)


def flash_attention(q, k, v, *, causal=True, block_q=256, block_k=512,
                    use_pallas="auto"):
    use, interp = _resolve(use_pallas)
    if use:
        return _fa.flash_attention(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interp,
        )
    return _ref.flash_attention(q, k, v, causal=causal, block_q=block_q,
                                block_k=block_k)


def decode_attention(q, k_cache, v_cache, lengths, *, block_k=1024,
                     use_pallas="auto"):
    use, interp = _resolve(use_pallas)
    if use:
        return _dec.decode_attention(
            q, k_cache, v_cache, lengths, block_k=block_k, interpret=interp
        )
    return _ref.decode_attention(q, k_cache, v_cache, lengths, block_k=block_k)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk=128, initial_state=None,
             use_pallas="auto"):
    use, interp = _resolve(use_pallas)
    if use:
        if initial_state is not None:
            raise ValueError(
                "the Pallas ssd_scan starts from a zero state; pass "
                "use_pallas=False to run the reference from initial_state")
        return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=interp)
    return _ref.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                         initial_state=initial_state)


def grouped_matmul(lhs, rhs, group_sizes, *, out_dtype, tile_rows,
                   use_pallas="auto"):
    """Rows ``[o_g, o_g + group_sizes[g])`` of ``lhs`` (M, K) times
    ``rhs[g]`` (K, N), for each group ``g`` in order from ``o_0 = 0``. Rows
    past the last group are not computed, and their values are undefined."""
    use, interp = _resolve(use_pallas)
    if use:
        return _gmm.grouped_matmul(lhs, rhs, group_sizes, out_dtype=out_dtype,
                                   tile_rows=tile_rows, interpret=interp)
    return lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=out_dtype)
