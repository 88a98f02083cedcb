"""The expert FFN's one rule (``ops/moe.py::expert_ffn``) against the
grouped products and the activation it replaces.  CPU; the Pallas kernels
run in interpret mode."""

import functools

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import moe

FFN_ROWS = 256
FFN_CASES = {  # group sizes over FFN_ROWS rows
    "every_group_held": (128, 128),
    "share_poisoned_past_live": (40, 30, 50, 20),
    "empty_group": (90, 0, 100, 66),
    "tile_two_groups_share": (100, 60, 50, 46),
    "every_row_in_one_group": (0, 256, 0),
}


def _composition(x, w_gate, w_up, w_down, sched, tile):
    """What ``expert_ffn`` replaces: three grouped products, SwiGLU
    between them in plain XLA over the whole buffer — or, without a gate
    (``w_gate`` None), two and the square of the positive part."""
    from ray_tpu.ops.layers import swiglu

    product = functools.partial(moe.grouped_matmul, sched=sched, tile=tile,
                                interpret=True)
    if w_gate is None:
        return product(jnp.square(jax.nn.relu(product(x, w_up))), w_down)
    return product(swiglu(product(x, w_gate), product(x, w_up)), w_down)


@functools.lru_cache(maxsize=None)
def _value_and_grads(rule, gated, case, dtype, tile):
    """``[y, d_x, *d_w]`` in float32 of ``rule`` ("fused": ``expert_ffn``,
    else the composition) on a case's seeded inputs in ``dtype``, on the
    live rows: one compiled program, kept for the process (the float32
    composition serves a case's float32 and bfloat16 tests)."""
    sizes = FFN_CASES[case]
    live, groups, d, m = sum(sizes), len(sizes), 32, 48
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    past = (jnp.arange(FFN_ROWS) >= live)[:, None]
    x, d_y = (jnp.where(past, jnp.nan, jax.random.normal(k, (FFN_ROWS, d)))
              for k in ks[:2])
    weights = tuple(jax.random.normal(k, shape) * 0.3 for k, shape in zip(
        ks[2:], [(groups, d, m), (groups, d, m), (groups, m, d)]))
    sched = moe.make_schedule(jnp.asarray(sizes), FFN_ROWS, tile)
    fn = (functools.partial(moe.expert_ffn, interpret=True)
          if rule == "fused" else _composition)
    gate = () if gated else (None,)

    @jax.jit
    def run(d_y, sched, *args):
        y, vjp = jax.vjp(lambda x, *w: fn(x, *gate, *w, sched, tile), *args)
        d_x, *d_w = vjp(d_y)
        return [a.astype(jnp.float32)
                for a in (y[:live], d_x[:live], *d_w)]

    return run(d_y.astype(dtype), sched, *(
        a.astype(dtype) for a in (x,) + weights[not gated:]))


@pytest.mark.parametrize("tile", [16, 128], ids=["tile16", "tile128"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(FFN_CASES))
@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "relu2"])
def test_expert_ffn_equals_the_composition_it_replaces(gated, case, dtype,
                                                       tile):
    """Value and all gradients (four; three of the expert without a gate)
    of the one rule against ``grouped_matmul``s with the activation
    between them, on the live rows (past them both are unspecified; of the
    share, rows and cotangent hold NaN there, so a kernel that read one
    unmasked would spread it into a weight's gradient).  In bfloat16 the
    rule rounds the activation once, from float32: it lies no further from
    the float32 composition than today's does."""
    f32 = jnp.float32
    got = _value_and_grads("fused", gated, case, dtype, tile)
    want = _value_and_grads("composition", gated, case, f32, tile)
    names = ("y", "d_x", "d_w_gate", "d_w_up", "d_w_down")
    names = names if gated else names[:2] + names[3:]
    assert len(got) == len(want) == len(names)
    if dtype == f32:
        for name, g, w in zip(names, got, want):
            assert bool(jnp.isfinite(g).all()), name
            assert float(jnp.abs(g - w).max()) <= 2e-6 * float(
                jnp.abs(w).max()), name
        return
    rms = lambda a: float(jnp.sqrt(jnp.mean(jnp.square(a))))
    today = _value_and_grads("composition", gated, case, dtype, tile)
    for name, g, t, w in zip(names, got, today, want):
        assert bool(jnp.isfinite(g).all()), name
        assert float(jnp.abs(g - w).max()) <= 3e-2 * float(
            jnp.abs(w).max()), name
        assert rms(g - w) <= 1.02 * rms(t - w), name
