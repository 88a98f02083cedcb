"""A chip's share of the dropless expert layer (``first_expert``, ``E' <
E``) with every row past the live ones poisoned, the token-side sum of a
share and the counter of the rows it reads.  CPU; the Pallas kernels run in
interpret mode."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Literal

from moe_layer import (
    D, E, K, T, against_the_loop, layer_inputs, per_token_loop, poisoned,
    seeded_experts)
from ray_tpu.ops import moe


@pytest.mark.parametrize("first,held,tile", [
    (0, 2, 16), (2, 3, 16), (5, 3, 128), (6, 2, 16), (0, 1, None)],
    ids=["first2", "middle3", "last3_tile128", "last2", "one_default_tile"])
@pytest.mark.parametrize("chunk", [1024, 40], ids=["one_chunk", "chunks"])
def test_share_equals_the_per_token_loop_on_poisoned_tails(
        first, held, tile, chunk, monkeypatch):
    """A chip's share of the layer (``first_expert``, ``E' < E``): value
    and EVERY gradient against the per-token loop, with the buffers'
    tails poisoned: nothing may read a row past the live ones unmasked.
    In chunks of 40 the loops over the live rows make several trips and
    a token's run of live choices crosses them."""
    poisoned(monkeypatch)
    monkeypatch.setattr(moe, "ROW_CHUNK", chunk)
    args = layer_inputs()
    cut = lambda a: tuple(a[:3]) + tuple(w[first:first + held]
                                         for w in a[3:])
    layer = lambda *a: moe.moe_block(*cut(a), num_selected=K, tile=tile,
                                     first_expert=first)
    loop = lambda *a: per_token_loop(*cut(a), first=first)
    stats, got = against_the_loop(layer, loop, args, (first, held))
    assert float(stats["dropped"]) == 0
    assert 0 < float(stats["held_share"]) < 1
    for g in got[3:]:  # the absent experts' tensors got no gradient
        assert float(jnp.abs(g[:first]).max(initial=0)) == 0
        assert float(jnp.abs(g[first + held:]).max(initial=0)) == 0


@pytest.mark.parametrize("chunk", [1024, 64], ids=["one_chunk", "chunks"])
def test_share_that_every_token_chooses_is_exact_at_full_buffer(
        chunk, monkeypatch):
    """There is no capacity: when every token chooses only held experts
    the live rows are ALL ``T * k`` of the buffer, the loops run to its
    end, nothing is dropped and value and gradients are the loop's."""
    poisoned(monkeypatch)
    monkeypatch.setattr(moe, "ROW_CHUNK", chunk)
    x, norm, router, w_gate, w_up, w_down = layer_inputs()
    x = jnp.abs(x)
    router = jnp.zeros((D, E)).at[:, 2:2 + K].set(
        1.0 + 0.1 * jnp.arange(K))       # everyone to experts 2, 3, 4
    args = (x, jnp.ones_like(norm), router, w_gate, w_up, w_down)
    cut = lambda a: tuple(a[:3]) + tuple(w[2:6] for w in a[3:])
    layer = lambda *a: moe.moe_block(*cut(a), num_selected=K, tile=16,
                                     first_expert=2)
    loop = lambda *a: per_token_loop(*cut(a), first=2)
    stats, _ = against_the_loop(layer, loop, args, "every token's")
    assert float(stats["held_share"]) == 1.0
    assert float(stats["dropped"]) == 0


def _token_sum_inputs(live, dtype, tokens=41, k=4, d=24, seed=0):
    """``rows (n, d)`` with NaN from ``live`` on, ``slot_row (tokens, k)``
    a permutation of the rows in which token 0 has ``k`` live choices,
    token 1 one and token 2 none (as far as ``live`` allows), the others
    what the seed deals them, and float32 ``weights``."""
    n = tokens * k
    rng = np.random.default_rng(seed)
    alive, dead = (list(rng.permutation(np.arange(lo, hi)))
                   for lo, hi in ((0, live), (live, n)))
    slot_row = np.full((tokens, k), -1)

    def deal(token, pile, count):
        count = min(count, len(pile))
        free = np.flatnonzero(slot_row[token] < 0)
        for j in rng.permutation(free)[:count]:
            slot_row[token, j] = pile.pop()

    deal(0, alive, k)
    deal(1, alive, 1), deal(1, dead, k - 1)
    deal(2, dead, k)
    rest = list(rng.permutation(alive + dead))
    for token in range(tokens):
        deal(token, rest, k)
    assert sorted(slot_row.reshape(-1)) == list(range(n))
    rows = rng.standard_normal((n, d)).astype(np.float32)
    rows[live:] = np.nan
    weights = rng.uniform(0.1, 1.0, (tokens, k)).astype(np.float32)
    return (jnp.asarray(rows, dtype), jnp.asarray(slot_row, jnp.int32),
            jnp.asarray(weights))


@pytest.mark.parametrize("chunk", [16, 1024], ids=["chunks", "one_chunk"])
@pytest.mark.parametrize("weighted", [True, False], ids=["gates", "ones"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("live", [0, 20, 82, 164],
                         ids=["none", "eighth", "half", "all"])
def test_token_sum_equals_a_loop_over_the_live_choices(
        live, dtype, weighted, chunk, monkeypatch):
    """The one token-side sum of a share (``_combine``'s forward with the
    gates, ``_dispatch``'s gradient without) against a loop over each
    token's choices, with every row from ``live`` on AND the buffer of
    the runs poisoned: no live rows, an eighth, half and all ``T * k`` of
    them; tokens with 0, 1 and ``k`` live choices; runs that cross the
    chunks of 16 (164 slots: the last trip runs over the one before it);
    24 columns."""
    poisoned(monkeypatch)
    monkeypatch.setattr(moe, "ROW_CHUNK", chunk)
    rows, slot_row, weights = _token_sum_inputs(live, dtype)
    got = moe._token_sum(rows, slot_row, weights if weighted else None,
                         jnp.int32(live))
    assert got.dtype == dtype and got.shape == (41, 24)
    want = np.zeros((41, 24), np.float32)
    for t, choices in enumerate(np.asarray(slot_row)):
        for j, row in enumerate(choices):
            if row < live:
                want[t] += np.asarray(rows[row], np.float32) * (
                    np.float32(weights[t, j]) if weighted else 1.0)
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    if live < 164:  # a token without a live choice reads exact zeros
        assert (got[2] == 0).all()
    tol = 1e-6 if dtype == jnp.float32 else 2.0 ** -8
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0)


@pytest.mark.parametrize("chunk", [1024, 32], ids=["one_chunk", "chunks"])
@pytest.mark.parametrize("first,held", [(0, E), (0, 2), (5, 3)],
                         ids=["every_expert", "first2", "last3"])
def test_token_rows_read_share_reads_what_the_index_says(
        first, held, chunk, monkeypatch):
    """The counter is the code's own: a token-side sum of a share fetches
    ``chunk + k - 1`` rows a trip over the live rows and ``T`` at the
    runs' ends; where every expert is held it is the one gather of a row
    a (token, choice): 1."""
    monkeypatch.setattr(moe, "ROW_CHUNK", chunk)
    args = layer_inputs()
    _, stats = moe.moe_block(
        *args[:3], *(w[first:first + held] for w in args[3:]),
        num_selected=K, tile=16, first_expert=first)
    live = int(np.bincount(np.asarray(seeded_experts()).reshape(-1),
                           minlength=E)[first:first + held].sum())
    trip = min(chunk, T * K)
    want = 1.0 if held == E else (
        -(-live // trip) * (trip + K - 1) + T) / (T * K)
    assert float(stats["token_rows_read_share"]) == pytest.approx(want)
    assert float(stats["held_share"]) == pytest.approx(live / (T * K))


# Two expert layers as ONE ``lax.scan`` under the layer checkpoint, a share
# of the experts held, and a nonlinearity behind the block, so that the
# backward pass reruns the combine as well (a layer of several residual
# streams does): every call site of ``_row_buffer`` in both loops.
_SCAN_FIRST, _SCAN_HELD = 2, 3


def _two_layer_scan(share):
    """``(loss, arguments)``: the scan's ``sum(out ** 2)`` as a function of
    the tokens and the two layers' stacked tensors — of the chip's share
    (``first_expert``, ``live`` the device's), or every expert held
    (``live`` None) with the absent experts' down weights at zero, which
    adds what a choice nobody computes adds."""
    x = layer_inputs(0)[0]
    stacked = tuple(jnp.stack(pair) for pair in zip(layer_inputs(0)[1:],
                                                    layer_inputs(1)[1:]))
    held = slice(_SCAN_FIRST, _SCAN_FIRST + _SCAN_HELD)
    here = jnp.zeros((E, 1, 1)).at[held].set(1.0)

    @functools.partial(
        jax.checkpoint, policy=jax.checkpoint_policies.save_only_these_names(
            *moe.SAVED_RESIDUALS))
    def layer(x, tensors):
        norm, router, w_gate, w_up, w_down = tensors
        if share:
            experts = (w_gate[held], w_up[held], w_down[held])
        else:
            experts = (w_gate, w_up, w_down * here)
        out, _ = moe.moe_block(x, norm, router, *experts, num_selected=K,
                               tile=16,
                               first_expert=_SCAN_FIRST if share else 0)
        return jnp.tanh(out), None

    def loss(x, *stacked):
        return jnp.sum(jax.lax.scan(layer, x, stacked)[0] ** 2)

    return loss, (x, *stacked)


# Who asked for a buffer, by the scope its call stands under and whether it
# is the layer's own pass (a ``custom_vjp_call``: forward, or rerun under
# the checkpoint) or a gradient rule's equations.
_SITE_OF = {("moe_dispatch", True): "_dispatch",
            ("moe_combine", True): "_live_token_sum",
            ("moe_combine", False): "_combine_bwd",
            ("moe_dispatch", False): "_live_token_sum"}


def _row_buffer_calls(jaxpr):
    """The ``moe_row_buffer`` calls under each ``scan`` of ``jaxpr`` that
    has any, a list a scan: ``(site, operand, shape, dtype)`` with ``site``
    the function that asked for the buffer (``_SITE_OF``) and ``operand``
    the variable of the scan's BODY the call's operand is (followed out of
    the calls in between), or ``const`` / ``carry`` / ``xs`` where it is
    none the body made."""
    scans = []

    def inner_jaxprs(eqn):
        for value in eqn.params.values():
            value = getattr(value, "jaxpr", value)
            if hasattr(value, "eqns") and hasattr(value, "invars"):
                yield value

    def walk(jaxpr, made, calls, scopes="", own_pass=False):
        for var in jaxpr.constvars:
            made[var] = "const"
        for eqn in jaxpr.eqns:
            operands = ["const" if isinstance(v, Literal)
                        else made.get(v, "const") for v in eqn.invars]
            under = f"{scopes}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "scan":
                body = eqn.params["jaxpr"].jaxpr
                consts, carry = (eqn.params["num_consts"],
                                 eqn.params["num_carry"])
                kinds = (["const"] * consts + ["carry"] * carry
                         + ["xs"] * (len(body.invars) - consts - carry))
                found = walk(body, dict(zip(body.invars, kinds)), [])
                if found:
                    scans.append(found)
            elif (eqn.primitive.name == "pallas_call"
                  and eqn.params["name"] == "moe_row_buffer"):
                scope, = (s for s in ("moe_dispatch", "moe_combine")
                          if s in under)
                out = eqn.outvars[0].aval
                calls.append((_SITE_OF[scope, own_pass],
                              operands[0] if operands else "const",
                              out.shape, out.dtype))
            else:
                for sub in inner_jaxprs(eqn):
                    # a call hands its operands in one for one; whatever
                    # else (a loop's body) makes its own
                    known = (dict(zip(sub.invars, operands))
                             if len(sub.invars) == len(operands) else {})
                    walk(sub, {v: known.get(v, v) for v in sub.invars},
                         calls, under,
                         own_pass or eqn.primitive.name == "custom_vjp_call")
            for var in eqn.outvars:
                made[var] = var
        return calls

    assert not walk(jaxpr, {}, []), "a row buffer outside every scan"
    return scans


@functools.lru_cache(maxsize=None)
def _scanned_row_buffers():
    loss, args = _two_layer_scan(share=True)
    return _row_buffer_calls(jax.make_jaxpr(jax.value_and_grad(
        loss, argnums=range(6)))(*args).jaxpr)


@pytest.mark.parametrize(
    "site", ["_dispatch", "_live_token_sum", "_combine_bwd"])
def test_row_buffer_is_made_by_the_layer_that_fills_it(site):
    """The two properties the copies of a whole row buffer turned on
    (PERF.md §6, PR 75), held on the jaxpr of a differentiated scan of two
    layers: every ``moe_row_buffer`` call takes an operand that an equation
    of the scan's BODY made — a call without one depends on nothing, the
    scan's partial evaluation moves it out of the forward loop and hands
    the buffer in as a constant, and each layer copies it whole before
    writing into it — and no two calls of a body take the same operand at
    the same shape and dtype, which the compiler's common-subexpression
    pass would make ONE buffer under two loops.  The forward loop holds the
    dispatch's and the combine's sum; the backward loop their reruns, the
    combine's gradient and the dispatch's gradient's sum."""
    forward, backward = _scanned_row_buffers()
    assert sorted(c[0] for c in forward) == [
        "_dispatch", "_live_token_sum"]
    assert sorted(c[0] for c in backward) == [
        "_combine_bwd", "_dispatch", "_live_token_sum", "_live_token_sum"]
    for calls in (forward, backward):
        for called_by, operand, shape, _ in calls:
            if called_by == site:
                assert not isinstance(operand, str), (site, operand)
                assert shape == (T * K, D)
        keys = [(id(operand), shape, str(dtype))
                for _, operand, shape, dtype in calls]
        assert len(set(keys)) == len(keys)


def test_scan_of_two_share_layers_equals_the_scan_with_every_expert_held():
    """The same scan, value and every gradient, against the form in which
    every expert is held (``live`` None: no buffer, no loop over live rows)
    and the absent experts' down weights are zero: the buffers' real
    kernel, its unread operand included, in interpret mode."""
    (share, args), (whole, _) = (_two_layer_scan(s) for s in (True, False))
    got, want = (jax.jit(jax.value_and_grad(f, argnums=range(6)))(*args)
                 for f in (share, whole))
    assert float(abs(got[0] - want[0])) < 1e-5 * float(want[0])
    for name, g, r in zip(("x", "norm", "router", "w_gate", "w_up",
                           "w_down"), got[1], want[1]):
        assert bool(jnp.isfinite(g).all()), name
        assert float(jnp.abs(g - r).max()) < 1e-5 * float(
            jnp.abs(r).max()) + 1e-6, name


# -- the shares of a softmax-routed layer add up to the uncut layer ------------

@functools.partial(jax.jit, static_argnums=(2, 3))
def _softmax_share(p, first, held, k):
    """What the chip that holds experts ``first .. first + held - 1`` adds
    for its tokens: ``moe_block`` routing over ALL the experts, ``k`` a
    token, gates renormalised over the chosen, no shared expert."""
    return moe.moe_block(
        p["x"], p["mlp_norm"], p["router"], *(
            jax.lax.dynamic_slice_in_dim(p[w], first, held)
            for w in ("w_gate", "w_up", "w_down")),
        num_selected=k, norm_eps=1e-6, norm_topk_prob=True,
        scoring="softmax", first_expert=first, residual=False)


@pytest.mark.parametrize("experts,k,chips", [(128, 8, 8), (16, 4, 4)],
                         ids=["sdar-128-top8-of-8-chips", "16-top4-of-4"])
def test_the_shares_of_a_softmax_router_add_up_to_the_uncut_layer(
        experts, k, chips):
    """At a configuration's router (SDAR's: 128 wide, 8 a token,
    renormalised, no shared expert, 16 experts a chip of 8): the parts all
    the chips give, summed, are the plain reference's uncut layer; every
    share routes over all the experts and drops nothing; one share alone is
    the reference's share."""
    from benchmark.reference import sdar_block_diffusion as plain
    from benchmark.reference.decoder import rms_norm

    rng = np.random.default_rng(experts)
    n = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa
    d, m, held = 32, 16, experts // chips
    p = {"x": n(96, d), "mlp_norm": 1.0 + 0.1 * n(d),
         "router": n(d, experts) * d ** -0.5,
         "w_gate": n(experts, d, m) * d ** -0.5,
         "w_up": n(experts, d, m) * d ** -0.5,
         "w_down": n(experts, m, d) * m ** -0.5}
    parts = [_softmax_share(p, first, held, k)
             for first in range(0, experts, held)]
    h = rms_norm(p["x"], p["mlp_norm"], 1e-6)
    whole, chosen, _ = plain.expert_ffn(h[None], p, k=k, renormalise=True,
                                        first=0)
    np.testing.assert_allclose(sum(part for part, _ in parts), whole[0],
                               atol=2e-5)
    stats = [s for _, s in parts]
    assert sum(float(s["held_share"]) for s in stats) == pytest.approx(1.0)
    assert all(float(s["dropped"]) == 0.0 for s in stats)
    for s in stats:     # every share routes over all the experts
        np.testing.assert_array_equal(
            s["counts"], np.bincount(np.asarray(chosen).ravel(),
                                     minlength=experts))
    alone, _, _ = plain.expert_ffn(
        h[None], {**p, **{w: p[w][held:2 * held]
                          for w in ("w_gate", "w_up", "w_down")}},
        k=k, renormalise=True, first=held)
    np.testing.assert_allclose(parts[1][0], alone[0], atol=2e-5)
