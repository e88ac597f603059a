"""The plain reference the served logits are compared with, and its control.

``Num`` is the arithmetic a family's ``last_logits`` runs in: float32 with
``Precision.HIGHEST`` products for the reference; for the control, every
weight and every operand of a product rounded to float8 (e4m3, one scale per
tensor), the precision below the configuration's bfloat16. The reference
takes only the benchmark's own host weights and the invocation's tokens.

The number compared, ``logit_err``, is the largest gap between a returned
logit and the reference's, over the invocations checked, each gap divided by
the root mean square of the reference's whole last-position row.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
_E4M3_MAX = 448.0


def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / _E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


class Num:
    def __init__(self, control: bool = False):
        self.control = control

    def w(self, x):
        x = x.astype(F32)
        return _fp8(x) if self.control else x

    def dot(self, spec, a, b):
        if self.control:
            a, b = _fp8(a), _fp8(b)
        return jnp.einsum(spec, a.astype(F32), b.astype(F32),
                          precision=lax.Precision.HIGHEST)


def last_rows(family, cfg, trees: Sequence, tokens: Sequence[np.ndarray],
              fn_of: Sequence[int], *, control: bool, block: int,
              device) -> List[np.ndarray]:
    """Last-position logits (V,) for each sample: tokens ``tokens[i]``
    through function ``fn_of[i]``'s weights ``trees[fn_of[i]]``. Samples
    of one function run ``block`` at a time; leaves that functions share
    go to the device once."""
    num = Num(control)
    fwd = jax.jit(lambda p, t: family.last_logits(cfg, p, t, num))
    on_dev: Dict[int, jax.Array] = {}

    def put(x):
        if id(x) not in on_dev:
            on_dev[id(x)] = jax.device_put(x, device)
        return on_dev[id(x)]

    out: List[np.ndarray] = [None] * len(tokens)
    for f in sorted(set(fn_of)):
        idx = [i for i, g in enumerate(fn_of) if g == f]
        params = jax.tree_util.tree_map(put, trees[f])
        for lo in range(0, len(idx), block):
            part = idx[lo:lo + block]
            toks = np.concatenate([tokens[i] for i in part], axis=0)
            if len(part) < block:  # one compiled shape for every block
                toks = np.concatenate(
                    [toks, np.repeat(toks[-1:], block - len(part), 0)], 0)
            rows = np.asarray(fwd(params, jax.device_put(toks, device)))
            for j, i in enumerate(part):
                out[i] = rows[j]
        del params
    return out


def logit_err(got: Sequence[np.ndarray], rows: Sequence[np.ndarray]) -> float:
    """Largest gap of a returned logit to the reference's row, over the
    row's root mean square; ``got[i]`` holds the row's first logits."""
    worst = 0.0
    for g, r in zip(got, rows):
        g = np.asarray(g, np.float64).reshape(-1)
        r = np.asarray(r, np.float64)
        if not np.isfinite(g).all():
            return float("inf")
        rms = float(np.sqrt(np.mean(r * r)))
        worst = max(worst, float(np.max(np.abs(g - r[:g.size]))) / rms)
    return worst
