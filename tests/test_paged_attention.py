"""Paged (block-table) decode attention kernel vs the oracles.

Convention from test_ops.py: every kernel is pinned against an XLA/host
reference, pallas running in interpret mode on the CPU backend — the
same code path that compiles for TPU.  The randomized battery covers
arbitrary (shuffled, non-contiguous) block tables, ragged last blocks,
padding table entries past the context, trailing-window masking, and
the ``window=1`` exact-gather identity the serving engine's bitwise
pin rides on.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.attention import mha_reference
from ray_tpu.ops.paged_attention import (
    paged_attention, paged_attention_reference)


def _random_paged(rng, B, h, d, bs, num_blocks, max_ctx):
    """Random cache + per-seq block tables (shuffled physical ids,
    ragged lengths, arbitrary padding entries past the last page)."""
    q = jnp.asarray(rng.normal(size=(B, h, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(num_blocks, h, bs, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(num_blocks, h, bs, d)), jnp.float32)
    cls = rng.integers(1, max_ctx + 1, size=B).astype(np.int32)
    width = -(-int(cls.max()) // bs)
    perm = rng.permutation(num_blocks)
    assert B * width <= num_blocks, "test sizing: disjoint tables"
    bt = perm[: B * width].reshape(B, width).astype(np.int32)
    # Overwrite the dead tail of each row with arbitrary (valid) ids:
    # the kernel must never read meaning into entries past the context.
    for b in range(B):
        pages = -(-int(cls[b]) // bs)
        bt[b, pages:] = rng.integers(0, num_blocks, size=width - pages)
    return q, kc, vc, bt, cls


def _gathered(kc, vc, bt, cls, b, bs):
    n = int(cls[b])
    pages = bt[b, : -(-n // bs)]
    # pages are head-major (h, bs, d): lay the context out (n, h, d).
    k = np.concatenate(list(np.asarray(kc)[pages]), axis=1)[:, :n]
    v = np.concatenate(list(np.asarray(vc)[pages]), axis=1)[:, :n]
    return (jnp.asarray(k.transpose(1, 0, 2)),
            jnp.asarray(v.transpose(1, 0, 2)), n)


@pytest.mark.parametrize("h,d,bs", [(1, 32, 8), (4, 32, 8), (2, 64, 16)])
def test_paged_attention_matches_mha_reference(h, d, bs):
    """Randomized block tables (incl. ragged last blocks): the paged
    kernel must match the contiguous-gather mha_reference oracle."""
    rng = np.random.default_rng(42)
    for trial in range(3):
        q, kc, vc, bt, cls = _random_paged(
            rng, B=3, h=h, d=d, bs=bs, num_blocks=24, max_ctx=5 * bs - 3)
        out = paged_attention(q, kc, vc, bt, cls, interpret=True)
        for b in range(q.shape[0]):
            k, v, n = _gathered(kc, vc, bt, cls, b, bs)
            # One decode query at position n-1 attending to the whole
            # context == causal attention with q_offset = n-1.
            ref = jax.jit(lambda q, k, v: mha_reference(
                q, k, v, causal=True, q_offset=n - 1))(
                    q[b][None, None], k[None], v[None])
            assert float(jnp.max(jnp.abs(out[b] - ref[0, 0]))) < 1e-5, \
                (trial, b)


def test_paged_attention_matches_xla_reference_and_window():
    rng = np.random.default_rng(7)
    q, kc, vc, bt, cls = _random_paged(
        rng, B=4, h=2, d=16, bs=8, num_blocks=32, max_ctx=29)
    for window in (0, 1, 5, 13):
        out = paged_attention(q, kc, vc, bt, cls, window=window,
                              interpret=True)
        ref = paged_attention_reference(q, kc, vc, bt, cls,
                                        window=window)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5, window


def test_paged_attention_window1_is_bitwise_gather():
    """window=1: softmax over a single position is exactly 1.0, so the
    output is BITWISE the stored v row — the identity the paged decode
    mode's greedy-chain pin is built on."""
    rng = np.random.default_rng(3)
    q, kc, vc, bt, cls = _random_paged(
        rng, B=5, h=1, d=32, bs=8, num_blocks=48, max_ctx=40)
    out = np.asarray(paged_attention(q, kc, vc, bt, cls, window=1,
                                     interpret=True))
    for b in range(out.shape[0]):
        n = int(cls[b])
        blk = int(bt[b, (n - 1) // 8])
        last = np.asarray(vc)[blk, :, (n - 1) % 8]
        assert (out[b] == last).all(), b


def test_paged_attention_ragged_single_token_context():
    """context_len=1 with a one-entry table: the smallest legal shape
    (a request admitted with a single prompt token)."""
    rng = np.random.default_rng(11)
    kc = jnp.asarray(rng.normal(size=(4, 1, 8, 16)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(4, 1, 8, 16)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, 1, 16)), jnp.float32)
    bt = np.asarray([[2]], np.int32)
    cls = np.asarray([1], np.int32)
    out = paged_attention(q, kc, vc, bt, cls, interpret=True)
    # Softmax over one position: exactly the first row of block 2.
    assert (np.asarray(out)[0] == np.asarray(vc)[2, :, 0]).all()


def test_paged_attention_interpret_default_off_tpu():
    """interpret=None resolves to interpret mode off-TPU (the repo
    convention: the same kernel path is tested on CPU)."""
    assert jax.default_backend() != "tpu"
    rng = np.random.default_rng(1)
    q, kc, vc, bt, cls = _random_paged(
        rng, B=2, h=1, d=16, bs=8, num_blocks=16, max_ctx=20)
    out = paged_attention(q, kc, vc, bt, cls)  # no explicit interpret
    ref = paged_attention_reference(q, kc, vc, bt, cls)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5
