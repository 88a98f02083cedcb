"""What the decoder knows of a block: the record a mixer or FFN module ends
in (``Block``), a tensor it holds (``Param``) with the closed set of
initialisers one may name, what every block is handed (``Ctx``), and how a
layer's step statistics join what the scan carries (``fold``)."""

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh


# ---- initialisers: ``(key, shape with the layer dim) -> float32`` ----------
# ``init_params`` draws no key for ``ones`` (a norm's weight never took one)
# and the next key for every other, whether it uses it or not: a tensor
# draws what it always drew.

def normal(key, shape, fan_in=None):
    """Scaled normal of a matrix, stacked or not; fan-in: its rows."""
    fan_in = shape[-2] if fan_in is None else fan_in
    return jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)


def residual_out(cfg):
    """``normal``, of a projection whose product a block adds to the
    residual: at ``cfg.residual_init_scale`` of it where the model's public
    file asks for that (``rescale_prenorm_residual``), else ``normal``
    itself."""
    scale = cfg.residual_init_scale
    if scale == 1.0:
        return normal
    return lambda key, shape: normal(key, shape) * scale


def ones(key, shape):
    return jnp.ones(shape, jnp.float32)


def constant(value: float):
    """``value`` everywhere (a key is drawn for it: ``ones`` alone takes
    none): a norm's gain that starts off 1."""
    if value == 1.0:
        return ones
    return lambda key, shape: jnp.full(shape, value, jnp.float32)


def keyed_ones(key, shape):
    """1, with a key drawn: Mamba's D and the stream maps' scales took one."""
    return ones(key, shape)


def conv(width: int):
    """A depthwise convolution of ``width`` taps as torch's ``Conv1d``:
    uniform within 1/sqrt(width)."""
    def init(key, shape):
        u = jax.random.uniform(key, shape, jnp.float32)
        return (2.0 * u - 1.0) * width ** -0.5
    return init


def dt_bias(key, shape):
    """The Mamba-2 reference code's: dt log-uniform in 1e-3..1e-1, through
    the inverse of the softplus it passes."""
    u = jax.random.uniform(key, shape, jnp.float32)
    dt = jnp.maximum(jnp.exp(jnp.log(1e-3) + u * jnp.log(1e2)), 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


def a_log(key, shape):
    """... A uniform in 1..16, kept as its log."""
    return jnp.log(1.0 + 15.0 * jax.random.uniform(key, shape, jnp.float32))


def s4d_a_log(key, shape):
    """Mamba-1's: ``A[c, n] = n + 1`` in every channel, kept as its log."""
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)), shape)


# What a bias is drawn at: off zero, so that a comparison with a reference
# can see it (a trained one starts at 0).
BIAS_STD = 0.02


def small(std: float):
    """Normal at ``std``, whatever the shape: a bias, a vector a layer."""
    return lambda key, shape: std * jax.random.normal(key, shape,
                                                      jnp.float32)


class Param(NamedTuple):
    """One tensor of a layer: its shape WITHOUT the stacked layer dim, its
    logical axes WITH it (``("layer", ...)``), its initialiser, and its
    dtype where that is not the model's ``param_dtype``."""
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: Callable = normal
    dtype: Any = None


class Ctx(NamedTuple):
    """What every block of a layer is handed.  ``cst(x, logical axes)`` is
    the sharding constraint under ``mesh``, the identity on one device;
    ``sp_manual``: the body runs in a region that is manual over 'sp' (the
    pipeline path): the sequence is device-local, RoPE starts at the rank's
    offset, ring / ulysses attention run inline."""
    cfg: Any
    mesh: Optional[Mesh]
    cst: Callable
    sp_manual: bool


@dataclasses.dataclass(frozen=True)
class Block:
    """A mixer or an FFN, as the decoder composes it.

    ``shapes(cfg) -> {name: Param}``: a layer's tensors, in the order
    ``init_params`` draws them.  ``apply(ctx, x, aux, lp, residual=True)``
    on the stream ``x`` (without ``residual``: what it would add, alone,
    for a layer that writes it into several streams itself), ``aux`` what
    the scan carries beside the stream, ``lp`` the layer's tensors; a mixer
    returns ``(x, aux)``, an FFN ``(x, aux, what the layer hands out of the
    scan or None)``.  ``saved``: the ``checkpoint_name``s it makes, which
    the layer checkpoint keeps.  ``scopes``: the ``jax.named_scope``s it
    opens, in order.  ``stats(cfg) -> {name: "sum" | "max" | "min" | "mean"}``: the
    float32 scalars it folds into ``aux`` and how layers combine each; they
    come back as step metrics under these names.  ``publishes``: the
    arrays a mixer makes for LATER layers, by name: its ``apply`` then
    returns ``(x, aux, {name: array})``.  ``reads``: the names a mixer
    takes, ``apply(..., shared={name: array})``, each the nearest earlier
    layer's publication; the decoder carries them from the one to the other
    (``models/llama.py::_scan_layers``) and refuses a model in which a
    reader has no publisher before it.  ``indexed``: its ``lp`` also holds
    ``layer_index``, the layer's place in the model counted from 0, a
    float32 scalar (a rule that depends on the depth reads it)."""
    shapes: Callable[[Any], Dict[str, Param]]
    apply: Callable
    saved: Tuple[str, ...] = ()
    scopes: Tuple[str, ...] = ()
    stats: Callable[[Any], Dict[str, str]] = lambda cfg: {}
    publishes: Tuple[str, ...] = ()
    reads: Tuple[str, ...] = ()
    indexed: bool = False


def _hand_on(ctx, x, aux, lp, residual: bool = True):
    """The stream as it came (nothing to add, without ``residual``)."""
    return (x if residual else jnp.zeros_like(x)), aux


# The empty block, ``none`` in both registries: no tensor, no scope, no
# saved residual, no statistic, and not one operation — the absent half of
# a layer that is a mixer OR an FFN alone.
EMPTY_MIXER = Block(lambda cfg: {}, _hand_on)
EMPTY_FFN = Block(lambda cfg: {}, lambda *a, **kw: (*_hand_on(*a, **kw), None))


def fold(aux, seen, how):
    """``aux`` with what one layer ``seen`` of the statistics ``how`` names:
    the larger for a ``max``, the smaller for a ``min`` (of numbers that are
    never positive: the scan starts every statistic at 0), else the sum
    (the decoder divides a ``mean`` by its layers at the end)."""
    join = {"max": jnp.maximum, "min": jnp.minimum}
    return {k: join.get(how[k], jnp.add)(v, seen[k])
            if k in how else v for k, v in aux.items()}
