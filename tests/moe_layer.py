"""What the suites of the dropless expert layer share (``tests/test_moe*.py``):
the seeded layer, the per-token loop it is held to, value and gradients as
one compiled program, and the poison of every row past the live ones.  Not
collected by pytest."""

import jax
import jax.numpy as jnp

from ray_tpu.ops import moe

T, D, E, K, M = 96, 32, 8, 3, 48
NAMES = ("x", "norm", "router", "w_gate", "w_up", "w_down")


def layer_inputs(seed=0, router_scale=0.5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (T, D)),
            1.0 + 0.3 * jax.random.normal(ks[5], (D,)),
            jax.random.normal(ks[1], (D, E)) * router_scale,
            jax.random.normal(ks[2], (E, D, M)) * 0.2,
            jax.random.normal(ks[3], (E, D, M)) * 0.2,
            jax.random.normal(ks[4], (E, M, D)) * 0.2)


def per_token_loop(x, norm, router, w_gate, w_up, w_down, k=K, first=0):
    """Every token through each of its k experts, one choice at a time;
    of the chip that holds the ``w_gate.shape[0]`` experts from ``first``
    on, a choice of an absent expert adds nothing."""
    held = w_gate.shape[0]
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * norm
    gates, experts = jax.lax.top_k(jax.nn.softmax(h @ router, -1), k)
    out = x
    for j in range(k):
        mine = (experts[:, j] >= first) & (experts[:, j] < first + held)
        e = jnp.clip(experts[:, j] - first, 0, held - 1)
        a = jnp.einsum("td,tdm->tm", h, w_gate[e])
        b = jnp.einsum("td,tdm->tm", h, w_up[e])
        y = jnp.einsum("tm,tmd->td", jax.nn.silu(a) * b, w_down[e])
        out = out + jnp.where(mine[:, None], gates[:, j:j + 1] * y, 0.0)
    return out


def value_and_gradients(layer, args):
    """``(out, stats)`` of a layer and every gradient of ``sum(out ** 2)``
    as ONE compiled program (traced here, so after any patch the caller
    put in)."""
    def scalar(*a):
        out, stats = layer(*a)
        return jnp.sum(out ** 2), (out, stats)

    (_, aux), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=range(6), has_aux=True))(*args)
    return aux, grads


_LOOPS = {}     # a loop's value and gradients, by the name a caller gave


def against_the_loop(layer, loop, args, same_loop):
    """Value and every gradient of ``sum(layer(...)[0] ** 2)`` against the
    loop's; ``(stats, gradients)`` of the layer.  The loop's value is a
    program of its own: returned from its gradients' program it changes
    what XLA fuses there, and the last bits of ``d_x`` with it.
    ``same_loop`` names the loop AND its arguments: cases that differ in
    the layer's tile or chunk alone run the loop's two programs once a
    process (the RESULTS are kept: ``poisoned`` clears every program)."""
    (out, stats), got = value_and_gradients(layer, args)
    if same_loop not in _LOOPS:
        _LOOPS[same_loop] = (jax.jit(loop)(*args), jax.jit(jax.grad(
            lambda *a: jnp.sum(loop(*a) ** 2), argnums=range(6)))(*args))
    want, ref = _LOOPS[same_loop]
    assert float(jnp.abs(out - want).max()) < 5e-6
    for name, g, r in zip(NAMES, got, ref):
        assert bool(jnp.isfinite(g).all()), name
        assert float(jnp.abs(g - r).max()) < 1e-6 * float(
            jnp.abs(r).max()) + 1e-6, name
    return stats, got


def seeded_experts():
    x, norm, router = layer_inputs()[:3]
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * norm
    return jax.lax.top_k(jax.nn.softmax(h @ router, -1), K)[1]


def poisoned(monkeypatch):
    """Every buffer the share's path allocates or a kernel leaves
    unvisited holds NaN past the live rows BEFORE anyone reads it: the
    row buffers under the two loops, and every output of the grouped
    kernels, the FFN's residuals among them (interpret mode hands out NaN
    there already; said again, so the test does not rest on it).  ``_live_token_sum`` keeps its traces: none
    from before the poison may serve."""
    jax.clear_caches()
    monkeypatch.setattr(
        moe, "_row_buffer", lambda shape, dtype, after: jnp.full(
            shape, jnp.nan, dtype))
    call = moe._gmm_call

    def call_with_poisoned_tails(form, operands, sched, *rest, **kw):
        row = jnp.arange(operands[0].shape[0])[:, None]
        return [jnp.where(row < sched.offsets[-1], out, jnp.nan)
                for out in call(form, operands, sched, *rest, **kw)]

    monkeypatch.setattr(moe, "_gmm_call", call_with_poisoned_tails)
