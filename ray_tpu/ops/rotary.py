"""RoPE on the heads side by side as a Pallas TPU kernel.

``rope_rotate`` is ``layers.apply_rope`` on x ``(b, s, heads x d)`` as a
projection leaves it and as the flash kernels read it (``ops/attention.py``:
a head is a lane block), for a head that fills whole lane blocks (``fits``:
``d % 128 == 0``).  A grid step takes a tile of rows in its whole width
— contiguous in memory — and the tables' block ``(rows, d)`` of the same
rows: ``C = [cos|cos]`` and ``S = [-sin|sin]``, float32 ``(s, d)``, made by
XLA from the ``(s, d / 2)`` tables, fetched once a row tile and read by every
head of it.  A lane's partner lies ``d / 2`` lanes away in its own head, on
either side, so the halves are swapped by ONE lane rotate of a head's block:

    y = x * C + rotate(x, d / 2) * S        float32, rounded once to x's type

the same two products and one sum as ``apply_rope`` (``a + (-b)`` is ``a -
b``), so the values agree to the bit.  The partner permutation is an
involution and ``S`` changes sign under it, so the backward pass is the same
kernel on the cotangent with ``S`` negated, ``g * C - rotate(g) * S`` — each
product rounded to x's type before the difference, as autodiff transposes
``apply_rope``'s two widenings of x — and the gradient agrees with
``apply_rope``'s likewise (``tests/test_ops.py``).  In plain XLA the same
rotation needs the tables as wide as x (no repeat along the lanes fuses) and
two slices off the lane tiles (PERF.md §6, PRs 56 and 58).

``scale``: the kernel that writes q can multiply it by the flash kernels'
pre-scale (``attention.q_prescale``) on its way out — rounded to x's type,
multiplied, rounded: the two roundings of ``(apply_rope(x) * scale)`` — so
that the multiply is no pass of its own over q; its backward pass multiplies
the cotangent on its way in, rounded as that product's own transpose rounds
it.  The kernels are named ``rope_fwd`` / ``rope_bwd``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention

_F32 = jnp.float32
_LANES = 128
_BLOCK_BYTES = 2 * 1024 * 1024   # x's block of a grid step, at most
_MAX_ROWS = 1024                 # ... and its rows: the tables' are float32


def fits(seq_len: int, head_dim: int) -> bool:
    """Whether the kernel serves a call: a head fills whole lane blocks
    (the flash kernels' own ``_in_place`` rule) and the rows whole
    sublane tiles."""
    return head_dim % _LANES == 0 and seq_len % 8 == 0


def lane_tables(cos: jax.Array, sin: jax.Array):
    """``(C, S) = ([cos|cos], [-sin|sin])``, float32 ``(s, d)``, from the
    ``(s, d / 2)`` tables: ``apply_rope`` is ``x * C + partner(x) * S``."""
    return (jnp.concatenate([cos, cos], -1).astype(_F32),
            jnp.concatenate([-sin, sin], -1).astype(_F32))


def _kernel(x_ref, c_ref, s_ref, o_ref, *, head_dim, transposed, scale):
    c, s = c_ref[...], s_ref[...]
    dtype = o_ref.dtype

    def rounded(a):   # to x's type and back: a rounding apply_rope makes
        return a.astype(dtype).astype(_F32)

    for lo in range(0, x_ref.shape[-1], head_dim):
        head = slice(lo, lo + head_dim)
        x = x_ref[:, head].astype(_F32)
        if not transposed:
            y = (x * c + pltpu.roll(x, head_dim // 2, 1) * s).astype(dtype)
            if scale is not None:
                y = (y.astype(_F32) * scale).astype(dtype)
        else:
            if scale is not None:
                x = rounded(x * scale)
            y = (rounded(x * c) - rounded(pltpu.roll(x, head_dim // 2, 1) * s)
                 ).astype(dtype)
        o_ref[:, head] = y


def _rows(seq_len: int, width: int, itemsize: int) -> int:
    """Rows of a grid step: the most that keep x's block under
    ``_BLOCK_BYTES`` and divide the sequence, a power of two times 8."""
    rows = 8
    while (rows * 2 <= _MAX_ROWS and seq_len % (rows * 2) == 0
           and rows * 2 * width * itemsize <= _BLOCK_BYTES):
        rows *= 2
    return rows


@functools.partial(jax.jit, static_argnames=(
    "head_dim", "transposed", "scale", "interpret"))
def _call(x, c, s, *, head_dim, transposed, scale, interpret):
    b, seq_len, width = x.shape
    rows = _rows(seq_len, width, x.dtype.itemsize)
    block = pl.BlockSpec((None, rows, width), lambda b_, i: (b_, i, 0))
    table = pl.BlockSpec((rows, head_dim), lambda b_, i: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, head_dim=head_dim, transposed=transposed,
                          scale=scale),
        grid=(b, seq_len // rows),
        in_specs=[block, table, table],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="rope_bwd" if transposed else "rope_fwd",
    )(x, c, s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def rope_rotate(x: jax.Array, c: jax.Array, s: jax.Array, head_dim: int,
                scale: Optional[float] = None) -> jax.Array:
    """``apply_rope`` of x ``(b, s, heads x head_dim)``'s 4-D view by
    ``lane_tables``' ``(C, S)``, in place of it, times ``scale`` where one
    is given (see the module's docstring); ``fits`` says where.  The tables
    take no gradient."""
    return _call(x, c, s, head_dim=head_dim, transposed=False, scale=scale,
                 interpret=attention._interpret_default())


def _rotate_fwd(x, c, s, head_dim, scale):
    return rope_rotate(x, c, s, head_dim, scale), (c, s)


def _rotate_bwd(head_dim, scale, tables, dy):
    dx = _call(dy, *tables, head_dim=head_dim, transposed=True, scale=scale,
               interpret=attention._interpret_default())
    return dx, None, None


rope_rotate.defvjp(_rotate_fwd, _rotate_bwd)
