"""Compile the main path for a described (not attached) TPU v5e 2x2.

Interpret-mode tests cannot see what the chip's compiler refuses (a dot
form Mosaic cannot parse, a block off the (8, 128) tiling, a step that
does not fit the device).  The TPU compiler is installed here and
compiles for a topology that is only described, so every kernel and
jitted step ``chip_smoke.py`` runs is compiled at its real widths —
a compile, not a run: nothing here says a step executes or what it costs.

The topology and everything built from it live in module-scoped
fixtures of THIS file: only the xdist worker that is handed this file
loads the TPU library (one process at a time may), and it compiles in
its own process.
"""

import dataclasses
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.paged_attention import paged_attention
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.train.core import (
    TrainState, default_optimizer, init_train_state, make_train_step,
    train_state_shardings)
from conftest import compiled_to_run


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it off around these.  And
    # these cases read what the OPTIMISING compiler makes (its text, its
    # memory analysis): tier-1's compile-to-check setting is set aside too.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with compiled_to_run():
        yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return make_mesh(MeshConfig(dp=1, fsdp=2, tp=2), devices=topo.devices)


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# -- kernels -----------------------------------------------------------------

@pytest.mark.parametrize("window", [0, 1])
@pytest.mark.parametrize("B,h,d,bs,blocks,dtype", [
    (8, 1, 32, 8, 32, jnp.float32),          # the engine's default
    (64, 1, 1024, 16, 4096, jnp.float32),    # the smoke's serve size
    (8, 32, 128, 16, 256, jnp.bfloat16),     # real widths
    (8, 8, 128, 32, 256, jnp.bfloat16),
])
def test_paged_attention_compiles(one_chip, B, h, d, bs, blocks, dtype,
                                  window):
    cache = _shape((blocks, h, bs, d), dtype, one_chip)
    compiled = jax.jit(
        lambda q, k, v, bt, cl: paged_attention(
            q, k, v, bt, cl, window=window, interpret=False)
    ).lower(_shape((B, h, d), dtype, one_chip), cache, cache,
            _shape((B, 16), jnp.int32, one_chip),
            _shape((B,), jnp.int32, one_chip)).compile()
    assert _has_kernel(compiled)


XING4_HEADS = ((1, 8192, 32, 192), 128)    # (q/k shape, v head size)


def _flash_shapes(shape, dv, sharding):
    x = _shape(shape, jnp.bfloat16, sharding)
    return x, x, _shape(shape[:3] + (dv,), jnp.bfloat16, sharding)


def _flash_sum(q, k, v):
    return flash_attention(q, k, v, causal=True,
                           interpret=False).astype(jnp.float32).sum()


_flash_grads = jax.grad(_flash_sum, argnums=(0, 1, 2))


@functools.lru_cache(maxsize=None)
def _lowered_flash_grads(shape, dv, sharding):
    """The backward pass at a shape, lowered once for the test that
    compiles it and the one that counts its kernels."""
    return jax.jit(_flash_grads).lower(*_flash_shapes(shape, dv, sharding))


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("shape,dv", [
    ((8, 2048, 32, 128), 128),  # the smoke's: one fetch tile, walked in sub-tiles
    ((4, 4096, 32, 128), 128),  # mistral7b-train-s4096: 2 x 2 tiles, one dead
    ((32, 512, 32, 128), 128),  # mistral7b-train-s512: narrow sub-tiles
    ((2, 4096, 16, 128), 128),  # a chip of deepseek7b-train-s4096-x4
    XING4_HEADS,                # xing4-train-s8192: q/k 192, v 128, 4 x 4 tiles
    ((1, 8192, 32, 64), 64),    # granite4h-train-s8192: half a lane block
], ids=lambda s: "x".join(map(str, s)) if isinstance(s, tuple) else f"v{s}")
def test_flash_attention_compiles(one_chip, shape, dv, grad):
    """Sub-tile slices, the masked part's concatenation, the clamped
    index maps, the backward kernel's row stats, transposed mask, strip
    loop, whole-sequence dk / dv and dq's product over the tile's FIRST
    dimension are what Mosaic could refuse."""
    lowered = (_lowered_flash_grads(shape, dv, one_chip) if grad
               else jax.jit(_flash_sum).lower(
                   *_flash_shapes(shape, dv, one_chip)))
    assert _has_kernel(lowered.compile())


def test_flash_backward_lowers_each_kernel_once(one_chip):
    """A backward pass at Xing4's head sizes holds ONE backward kernel
    (``flash_dkv``, which writes dq too; its interior tile is a loop
    inside the kernel, not a second call), no ``flash_dq``, and the
    forward that makes the residuals; of the forward's lane-replicated
    ``(b, h, sq, 128)`` log-sum-exp one lane a row reaches the backward,
    its ONE float32 operand (``delta`` is made inside it)."""
    text = _lowered_flash_grads(*XING4_HEADS, one_chip).as_text()
    assert [text.count(f'kernel_name = "{name}"')
            for name in ("flash_fwd", "flash_dkv", "flash_dq")] == [1, 1, 0]
    (call,) = [line for line in text.splitlines()
               if 'kernel_name = "flash_dkv"' in line]
    operands, results = call.rsplit(" : (", 1)[1].split(") -> (")
    (b, s, h, _), _ = XING4_HEADS
    assert operands.count(f"tensor<{b}x{h}x1x{s}xf32>") == 1    # lse
    assert operands.count("xf32>") == 1 and results.count("tensor<") == 3


@pytest.mark.parametrize("b,h,h_kv,window,s", [
    (1, 48, 8, None, 8192),   # trinity-train-s8192's full layer: groups of 6
    (1, 48, 8, 4096, 8192),   # ... and its four windowed ones
    (2, 32, 2, None, 8192),   # nemotronh-train-s8192: groups of 16
    (4, 32, 8, None, 8192),   # mistral7b-train-s4096's heads, at 8192
    (1, 32, 4, None, 8192),   # mellum2-train-s16384's full layer at 8192
    (1, 32, 4, 1024, 8192),   # ... and its three windowed ones, at 8192
    # ... at its own length: 16.8 MB of float32 dk / dv a KV head in VMEM
    (1, 32, 4, None, 16384),
], ids=lambda x: str(x))
def test_flash_reads_lane_block_heads_in_place(one_chip, b, h, h_kv, window,
                                               s):
    """At a head of 128 lanes the two kernels take q ``(b, s, h x 128)``
    and k, v ``(b, s, h_kv x 128)`` as the model leaves them: the lowered
    gradient holds each kernel once, NOTHING is transposed (``delta``'s
    float a row, the last, is made inside the backward kernel), and no k,
    v, dk or dv stands at q's head count — Mosaic takes the strided blocks
    and the composite ``rep x nq`` axis."""
    d = 128
    q = _shape((b, s, h, d), jnp.bfloat16, one_chip)
    kv = _shape((b, s, h_kv, d), jnp.bfloat16, one_chip)

    def summed(q, k, v):
        with jax.named_scope("attention"):
            return flash_attention(q, k, v, causal=True, window=window,
                                   interpret=False).astype(jnp.float32).sum()

    lowered = jax.jit(jax.grad(summed, argnums=(0, 1, 2))).lower(q, kv, kv)
    text = lowered.as_text()
    names = [n + ("_win" if window else "")
             for n in ("flash_fwd", "flash_dkv", "flash_dq")]
    assert [text.count(f'kernel_name = "{n}"') for n in names] == [1, 1, 0]
    assert "stablehlo.transpose" not in text
    assert f"tensor<{b}x{s}x{h * d}xbf16>" in text       # q where it stands
    assert f"tensor<{b}x{h}x{s}x{d}xbf16>" not in text   # nothing turned round
    compiled = lowered.compile()
    assert _has_kernel(compiled)
    hlo = compiled.as_text()
    assert not [line for line in hlo.splitlines()
                if " transpose(" in line and "bf16[" in line]
    calls = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2
    for line in calls:      # q-side operands at h heads, kv-side at h_kv
        widths = {int(w) for w in re.findall(
            rf"bf16\[{b},{s},(\d+)\]", line)}
        assert widths == {h * d, h_kv * d}, line
        assert f"bf16[{b},{h}," not in line


@pytest.mark.parametrize("shape,window", [
    ((1, 8192, 48, 128), 4096),  # trinity-train-s8192: a far tile of 4 x 4
    ((1, 8192, 48, 128), 1000),  # both edges inside one fetch tile
    ((4, 4096, 32, 128), 4000),  # an edge between two sub-tiles
    ((1, 16384, 32, 128), 1024),  # mellum2-train-s16384: half a fetch tile
], ids=lambda s: "x".join(map(str, s)) if isinstance(s, tuple) else f"w{s}")
def test_windowed_flash_attention_compiles_under_its_own_names(
        one_chip, shape, window):
    """The far edge's masks (a segment with an upper bound, one with both),
    the index maps clamped from both sides and the second straddling branch
    are what Mosaic could refuse; the two windowed kernels are named
    apart from the plain ones."""
    grads = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, interpret=False).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))
    lowered = jax.jit(grads).lower(*_flash_shapes(shape, 128, one_chip))
    text = lowered.as_text()
    assert [text.count(f'kernel_name = "{name}"') for name in (
        "flash_fwd_win", "flash_dkv_win", "flash_dq_win", "flash_fwd",
        "flash_dkv", "flash_dq")] == [1, 1, 0, 0, 0, 0]
    assert _has_kernel(lowered.compile())


def test_block_rule_flash_attention_compiles_under_its_own_names(one_chip):
    """SDAR's cell: two streams of 8192 positions, 32 heads on 4 KV heads of
    128, blocks of 4 — the shifts of the diagonal's test, the q axis over
    both streams, the noised stream's own keys as two more operands and a
    KV head's dk / dv of 16384 rows in VMEM are what Mosaic could refuse."""
    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16,
                              sharding=one_chip)
    grads = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, block=4, interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    lowered = jax.jit(grads).lower(q, kv, kv)
    text = lowered.as_text()
    assert [text.count(f'kernel_name = "{name}"') for name in (
        "flash_fwd_bd", "flash_dkv_bd", "flash_fwd", "flash_dkv")] == [
            1, 1, 0, 0]
    assert _has_kernel(lowered.compile())


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("k,n", [(2048, 1024), (1024, 2048)],
                         ids=["gate_up", "down"])
def test_grouped_matmul_compiles(one_chip, k, n, grad):
    """olmoe-train-s4096's grouped products: 131072 rows in 64 ragged
    groups at the tile ``choose_tiles`` picks; the masked stores, the
    transposed-weights product and the row contraction of ``moe_tgmm``
    are what Mosaic could refuse."""
    from ray_tpu.ops import moe

    rows, groups = 131072, 64
    tile = moe.choose_tiles(rows, groups)

    def f(x, w, sizes):
        sched = moe.make_schedule(sizes, rows, tile)
        return moe.grouped_matmul(x, w, sched, tile, False).astype(
            jnp.float32).sum()

    fn = jax.grad(f, argnums=(0, 1)) if grad else f
    compiled = jax.jit(fn).lower(
        _shape((rows, k), jnp.bfloat16, one_chip),
        _shape((groups, k, n), jnp.bfloat16, one_chip),
        _shape((groups,), jnp.int32, one_chip)).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("rows,groups,d,m,live,gated", [
    (131072, 32, 2048, 768, 8, True), (32768, 8, 3584, 1024, 8, True),
    (32768, 16, 2048, 1792, 2, True), (131072, 64, 2048, 1024, 1, True),
    (98304, 16, 2688, 1856, 8, False)],
    ids=["joyai", "xing4", "lfm2", "olmoe", "nemotronh"])
def test_expert_ffn_compiles(one_chip, rows, groups, d, m, live, gated):
    """The routed experts' FFN as one rule, value and all gradients, at the
    five expert cells' widths, static rows and tile: two weight blocks
    beside three output blocks in VMEM, SwiGLU and its derivative in the
    epilogues, the two-product sum and the masked stores of up to three
    outputs are what Mosaic could refuse; of the expert without a gate, a
    width (1856) that is no multiple of the lanes and whose weight block
    cannot be cut, the square and its derivative in the epilogues."""
    from ray_tpu.ops import moe

    tile = moe.choose_tiles(rows // live, groups)
    gate = () if gated else (None,)

    def f(x, sizes, *weights):
        sched = moe.make_schedule(sizes, rows, tile)
        return moe.expert_ffn(x, *gate, *weights, sched, tile,
                              False).astype(jnp.float32).sum()

    ups = [_shape((groups, d, m), jnp.bfloat16, one_chip)] * (1 + gated)
    text = jax.jit(jax.grad(f, argnums=(0, *range(2, 3 + len(ups))))).lower(
        _shape((rows, d), jnp.bfloat16, one_chip),
        _shape((groups,), jnp.int32, one_chip), *ups,
        _shape((groups, m, d), jnp.bfloat16, one_chip)).compile().as_text()
    for kernel in (("moe_gmm_swiglu", "moe_gmm_dswiglu", "moe_gmm_pair")
                   if gated else ("moe_gmm_relu2", "moe_gmm_drelu2")) + (
                       "moe_tgmm",):
        assert kernel in text, kernel


# -- whole train steps -------------------------------------------------------

@pytest.fixture
def as_on_chip(monkeypatch):
    """The model picks interpret mode from ``jax.default_backend()``,
    which is the CPU here: steer it to the branch a chip worker takes."""
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_interpret_default", lambda: False)


def _state_shapes(cfg, opt, shardings):
    """TrainState of ShapeDtypeStructs carrying ``shardings`` — a
    TrainState of them, or one sharding for every leaf (there is no
    device to hold an array, so nothing is initialised)."""
    shapes = jax.eval_shape(
        lambda k: init_train_state(k, cfg, opt), jax.random.PRNGKey(0))
    if not isinstance(shardings, TrainState):
        shardings = jax.tree.map(lambda _: shardings, shapes)
    return jax.tree.map(
        lambda s, sh: _shape(s.shape, s.dtype, sh), shapes, shardings)


def _smoke_cfg(**kw):
    import chip_smoke

    model = dict(chip_smoke.TRAIN_MODEL, **kw)
    model.pop("preset")
    model["param_dtype"] = jnp.dtype(model["param_dtype"])
    return LlamaConfig.llama2_7b(**model)


@functools.lru_cache(maxsize=None)
def _lowered_step(cfg, rows, positions, where):
    """The train step of ``cfg`` on ``rows`` x ``positions`` tokens under
    the default optimizer, lowered for ``where`` — the described chip
    (``one_chip``), or a mesh of the described chips (built with ``mesh=``
    and lowered with NO mesh context: how the smoke's loop calls it) —
    once a process."""
    opt = default_optimizer()
    if isinstance(where, jax.sharding.Mesh):
        step = make_train_step(cfg, opt, mesh=where)
        state = train_state_shardings(cfg, opt, where)
        where = NamedSharding(where, P(("dp", "fsdp")))
    else:
        step, state = make_train_step(cfg, opt), where
    return step.lower(
        _state_shapes(cfg, opt, state),
        {"tokens": _shape((rows, positions + 1), jnp.int32, where)})


@functools.lru_cache(maxsize=None)
def _compiled_step(cfg, rows, positions, where):
    """``_lowered_step`` compiled, ONCE a process: every case that reads a
    step's compiled text or its memory analysis takes it from here (a
    whole cell's step is a minute and a half, a layer's half a minute)."""
    return _lowered_step(cfg, rows, positions, where).compile()


def _smoke_step(where):
    """The smoke's config (7B widths, batch 8 x 2048), depth cut to 1
    layer to keep the compile short — the scanned layer body is the same
    program at any depth."""
    import chip_smoke

    return _compiled_step(_smoke_cfg(num_layers=1), chip_smoke.TRAIN_BATCH,
                          chip_smoke.TRAIN_SEQ, where)


def test_one_chip_train_step_compiles(one_chip, as_on_chip):
    compiled = _smoke_step(one_chip)
    assert _has_kernel(compiled)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_fsdp2_tp2_train_step_compiles(mesh4, as_on_chip):
    """The --chips 4 step, built with ``mesh=`` and lowered with NO mesh
    context (how the smoke's loop calls it)."""
    cfg, opt = _smoke_cfg(num_layers=1), default_optimizer()
    shardings = train_state_shardings(cfg, opt, mesh4)
    # adam's moments must live where their parameter lives, not on chip 0
    mu = shardings.opt_state[1][0].mu
    assert mu["layers"]["wq"] == shardings.params["layers"]["wq"]
    assert mu["lm_head"].spec == P("fsdp", "tp")
    compiled = _smoke_step(mesh4)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # fsdp gathers parameters and scatters gradients; tp reduces partial
    # matmul sums: a step without them was not partitioned.
    assert "all-gather" in text and "all-reduce" in text
    # 'tp' shards q's and k's lanes by heads: RoPE stays on the 4-D view
    # there — a roll of the lane axis crosses the shards: rotated flat, this
    # step read 29 collective-permutes, 24 of them the rolls'
    assert [op for op, _ in _collectives(text)].count(
        "collective-permute") == 5
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


@pytest.mark.parametrize("where,shard", [
    ("one_chip", (8, 2048, 32000)), ("mesh4", (4, 2048, 16000))],
    ids=["one_chip", "mesh4"])
def test_the_loss_writes_no_float32_array_of_the_logits_size(
        request, as_on_chip, where, shard):
    """The two smoke steps above (the programs they compiled, no new
    compile): the head writes its bf16 logits and NO op writes a float32
    array of their shape — a chip's shard of them on the mesh, batch over
    ``fsdp`` and vocabulary over ``tp`` — for the loss or its gradient.
    JAX's own gradient of ``log_softmax`` and a gather wrote ``log p``
    there, 2.1 GB on the one chip (PERF.md §6, PR 82).  And on the mesh no
    collective moves an array of the logits' rank: the loss's sums over
    ``tp`` are of one number a position."""
    text = _smoke_step(request.getfixturevalue(where)).as_text()
    logits = "[{},{},{}]".format(*shard)
    # an op's result type: what stands before its operands
    results = [line.split(" = ", 1)[1].split("(%", 1)[0]
               for line in _written(text)]
    assert [r for r in results if "bf16" + logits in r]
    assert not [r for r in results if "f32" + logits in r]
    if where == "mesh4":
        assert not [m for m in _collectives(text)
                    if "2048,16000" in m[1] or "2048,32000" in m[1]]


def _embed_twice_grad(cfg, mesh):
    """The table's gradient of a table read twice — a model's tokens and
    its predicted-ahead module's, one position on — compiled for ``mesh``
    at ``cfg``'s widths, four rows of 4096."""
    from ray_tpu.models.llama import _embed
    from ray_tpu.parallel.sharding import named_sharding

    def loss(table, tokens, ct):
        with jax.named_scope("embed"):
            x = _embed({"embed": table}, tokens[:, :-1], cfg, mesh, None)
            e = _embed({"embed": table}, tokens[:, 1:], cfg, mesh, None)
        return jnp.sum((x * ct + jnp.tanh(e) * ct).astype(jnp.float32))

    in_table = named_sharding(mesh, "vocab", "kernel_in")
    return jax.jit(jax.grad(loss), out_shardings=in_table).lower(
        _shape((cfg.vocab_size, cfg.embed_dim), jnp.float32, in_table),
        _shape((4, 4097), jnp.int32, named_sharding(mesh, "batch", "seq")),
        _shape((4, 4096, cfg.embed_dim), jnp.bfloat16,
               named_sharding(mesh, "batch", "seq", "embed"))).compile()


def _collectives(text):
    """(opcode, result's shapes) of every collective of a compiled text."""
    import re

    return [(m.group(2), m.group(1)) for m in re.finditer(
        r"= (.*?) (all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start)?\(", text)]


def test_embedding_over_fsdp2_tp2_gathers_no_table(mesh4):
    """DeepSeek's table (102400 x 4096, rows over tp, columns over fsdp):
    a chip keeps its (51200, 2048) block — no collective's result is as
    large as the block, let alone a row shard or the table — and scope
    ``embed`` multiplies nothing."""
    cfg = LlamaConfig.tiny(vocab_size=102400, embed_dim=4096,
                           dtype=jnp.bfloat16)
    text = _embed_twice_grad(cfg, mesh4).as_text()
    assert " scatter(" in text and " gather(" in text
    assert " convolution(" not in text and " dot(" not in text
    moved = _collectives(text)
    assert moved and not [
        m for m in moved if "51200" in m[1] or "102400" in m[1]], moved


def test_embedding_read_twice_moves_no_rows_between_chips(topo):
    """JoyAI's slice (64640 x 2048, whole on each of four ``ep`` ranks,
    a sequence a rank): XLA joins the two uses' scatter-adds before it
    partitions them, and joins them along the sequence — the table's
    all-reduce is the scope's only collective.  (Joined along the batch's
    rows, twelve collective-permutes move tokens and cotangents between
    the ranks.)"""
    mesh = make_mesh(MeshConfig(ep=4), devices=topo.devices)
    cfg = LlamaConfig.tiny(vocab_size=64640, embed_dim=2048,
                           dtype=jnp.bfloat16)
    text = _embed_twice_grad(cfg, mesh).as_text()
    assert text.count(" scatter(") == 1
    assert [m[0] for m in _collectives(text)] == ["all-reduce"]


def test_expert_parallel_train_step_compiles(mesh4, as_on_chip):
    """An expert layer on fsdp=2 x ep=2 (the mesh fixture's devices,
    re-meshed): the grouped-product kernels inside a region manual over
    every axis, at OLMoE's widths cut to one layer and 8 experts."""
    mesh = make_mesh(MeshConfig(fsdp=2, ep=2),
                     devices=list(mesh4.devices.flat))
    cfg = LlamaConfig(
        vocab_size=50304, embed_dim=2048, num_layers=1, num_heads=16,
        num_kv_heads=16, head_dim=128, mlp_dim=1024, num_experts=8,
        num_selected=2, qk_norm=True, norm_eps=1e-5, z_loss_coef=0.001,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    text = _compiled_step(cfg, 4, 2048, mesh).as_text()
    assert "moe_gmm" in text and "moe_tgmm" in text


def _granite_cfg(layer_types, vocab_size=4096):
    """granite-4.0-h-micro at its published widths, the vocabulary cut
    (so the head is not what is compiled), a layer of each kind named."""
    return LlamaConfig(
        vocab_size=vocab_size, embed_dim=2048, num_layers=len(layer_types),
        num_heads=32, num_kv_heads=8, head_dim=64, mlp_dim=8192,
        norm_eps=1e-5, layer_types=layer_types, ssm_heads=64,
        ssm_head_dim=64, ssm_state=128, ssm_groups=1, ssm_conv=4,
        ssm_chunk=256, position_embedding="nope",
        attention_multiplier=1 / 64, embedding_multiplier=12,
        residual_multiplier=0.22, logits_scaling=8, tie_embeddings=True,
        param_dtype=jnp.bfloat16)


@pytest.mark.parametrize("layer_types", [("mamba",), ("attention",)],
                         ids=["mamba", "attention-nope-head64"])
def test_granite_hybrid_layer_train_step_compiles(one_chip, as_on_chip,
                                                  layer_types):
    """One layer of each kind of granite-4.0-h-micro at its published
    widths and 8192 positions, as a train step: the Mamba-2 layer's scan
    is the ``ssd_fwd`` / ``ssd_bwd`` kernels (heads of 64 in pairs over
    the lanes, 8 pairs a grid step cut out of the block by a dynamic lane
    offset, the states of all heads in 2 MB of VMEM: what Mosaic could
    refuse) and fits; the attention layer's flash kernels take head size
    64 (half of Mosaic's minor dimension), 32 query heads on 8 KV heads,
    a softmax scale that is a given number, and no RoPE."""
    compiled = _compiled_step(_granite_cfg(layer_types), 1, 8192, one_chip)
    text = compiled.as_text()
    assert _has_kernel(compiled)
    assert ("ssd_fwd" in text and "ssd_bwd" in text) is (
        layer_types == ("mamba",))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8e9


def test_granite_hybrid_programs_lower_the_scan_kernels_once_a_use(
        one_chip, as_on_chip):
    """The structural guard of the cell's set-up budget (what is lowered
    is paid by every process, warm cache or not): granite's step program
    over BOTH Mamba runs and the attention layer, and the benchmark's
    check program (``loss_fn`` AND ``forward``: two forward passes),
    lowered for the described chip.  A kernel's body is traced once a
    process (``_fwd_call`` and ``_bwd_call`` are module-level ``jit``s);
    the step program holds the forward kernel four times (a Mamba run's
    forward pass, and its rematerialised forward, which also returns the
    states — two runs) and the backward kernel ONCE for both runs; the
    check program the forward kernel once for its four calls."""
    from benchmark.loops import train

    cfg = _granite_cfg(("mamba", "attention", "mamba"))
    opt = default_optimizer()
    state = _state_shapes(cfg, opt, one_chip)
    tokens = _shape((1, 8193), jnp.int32, one_chip)
    step = _lowered_step(cfg, 1, tokens.shape[1] - 1, one_chip).as_text()
    check = jax.jit(train.program_check(cfg, None)).lower(
        state.params, tokens).as_text()

    def kernels(text, name):
        return text.count(f'kernel_name = "{name}"')

    assert (kernels(step, "ssd_fwd"), kernels(step, "ssd_bwd")) == (4, 1)
    assert (kernels(check, "ssd_fwd"), kernels(check, "ssd_bwd")) == (1, 0)
    assert check.count("call @_fwd_call") == 4
    assert (kernels(step, "flash_fwd"), kernels(check, "flash_fwd")) == (1, 2)


@pytest.mark.parametrize("batch,groups,chunk", [(1, 1, 256), (2, 8, 128)],
                         ids=["granite-one-group", "nemotronh-8-groups"])
def test_state_space_scan_compiles_at_the_published_shapes(
        one_chip, as_on_chip, batch, groups, chunk):
    """``ssd_chunked``, value and the six gradients, at 8192 positions of
    64 heads x 64 with a state of 128: granite's one group in chunks of
    256 and Nemotron-H's 8 groups in chunks of 128, whose B and C blocks
    are cut out of ``(b, s, 8 * 128)`` at the grid step's group — a block
    index that is a quotient, outputs (the group's ``dB``, ``dC``) whose
    block changes along an ``arbitrary`` axis: what Mosaic could refuse.
    Both are the kernels, once forward and once backward."""
    from ray_tpu.ops.ssm import ssd_chunked

    def f(*t):
        return ssd_chunked(*t, chunk=chunk).astype(jnp.float32).sum()

    bc = _shape((batch, 8192, groups, 128), jnp.bfloat16, one_chip)
    heads = _shape((64,), jnp.float32, one_chip)
    compiled = jax.jit(jax.grad(f, argnums=range(6))).lower(
        _shape((batch, 8192, 64, 64), jnp.bfloat16, one_chip),
        _shape((batch, 8192, 64), jnp.float32, one_chip), heads, bc, bc,
        heads).compile()
    text = compiled.as_text()
    assert "ssd_fwd" in text and "ssd_bwd" in text


def test_the_grouped_norm_compiles_with_no_axis_of_groups(one_chip,
                                                          as_on_chip):
    """The ``ssm_out`` part of a Nemotron-H Mamba layer — the gated norm
    over 8 groups of 512, the output projection, the residual add — at 2
    x 8192 tokens, value and gradients: the norm is the ``gated_norm_fwd``
    / ``gated_norm_bwd`` kernels, lowered once a use, over the ``(tokens,
    4096)`` arrays as they stand (a block of 1024 rows x one group's 512
    lanes, the weight's gradient a tile: what Mosaic could refuse), and
    the compiled program holds NO float32 array with the groups as an
    axis of their own — the reshape to ``f32[2,8192,8,512]`` put the
    groups in the sublanes and cost a relayout of every intermediate."""
    import re

    from ray_tpu.ops.ssm import gated_rms_norm

    def ssm_out(y, z, norm, w, x):
        with jax.named_scope("ssm_out"):
            h = gated_rms_norm(y, z, norm, 1e-5, 8)
            return (x + h @ w).astype(jnp.float32).sum()

    bf16 = functools.partial(_shape, dtype=jnp.bfloat16, sharding=one_chip)
    lowered = jax.jit(jax.value_and_grad(ssm_out, argnums=(0, 1, 2, 3))
                      ).lower(bf16((2, 8192, 4096)), bf16((2, 8192, 4096)),
                              bf16((4096,)), bf16((4096, 2688)),
                              bf16((2, 8192, 2688)))
    text = lowered.as_text()
    assert [text.count(f'kernel_name = "gated_norm_{k}"')
            for k in ("fwd", "bwd")] == [1, 1]
    compiled = lowered.compile().as_text()
    assert "gated_norm_fwd" in compiled and "gated_norm_bwd" in compiled
    assert not re.search(r"f32\[[0-9,]*,8,512\]", compiled)


def _benchmark_cfg(name):
    """The program's config of a benchmark configuration file."""
    import json

    from benchmark.loops import train

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs", name + ".json")
    with open(path) as f:
        return train.program_config(json.load(f))


def _xing4_cfg(**kw):
    """Xing4.0-29B-A4B as ``xing4-train-s8192`` runs it (the benchmark's
    configuration file: hidden 3584, 4 residual streams mixed by maps from
    20 Sinkhorn rounds, latent attention at 192 / 128), without its
    predicted-ahead module and with the vocabulary cut, so that the
    layers are what is compiled."""
    import dataclasses

    cfg = _benchmark_cfg("xing4.0-29b-a4b-1of8")
    assert (cfg.embed_dim, cfg.hc_mult, cfg.hc_sinkhorn_iters) == (3584, 4, 20)
    return dataclasses.replace(cfg, vocab_size=4096, num_nextn=0, **kw)


_HC_KERNELS = ("hc_read_fwd", "hc_write_fwd", "hc_read_bwd", "hc_write_bwd")


def test_xing4_layer_train_step_compiles_with_the_stream_kernels(
        one_chip, as_on_chip):
    """One (latent, dense) layer of Xing4 at its published widths and 8192
    positions as a train step: both halves of both blocks are the ``hc_*``
    kernels — a tile of 256 tokens x 14336 lanes of the four streams in
    VMEM three times over in ``hc_read_bwd`` beside the projection's
    gradient (128, 14336) float32, strips cut out at a dynamic lane
    offset, the (256, 128) maps transposed both ways, 40 round states in
    a scratch: what Mosaic could refuse — and it fits."""
    cfg = _xing4_cfg(num_layers=1, leading_dense=1)
    assert cfg.kind_runs == ((("latent", "dense"), 1),)
    compiled = _compiled_step(cfg, 1, 8192, one_chip)
    text = compiled.as_text()
    assert all(name in text for name in _HC_KERNELS)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8e9


def test_xing4_programs_lower_the_stream_kernels_once_a_use(one_chip,
                                                            as_on_chip):
    """The structural guard of the cell's set-up budget, as granite's is:
    Xing4's step program over a dense and an expert run, and the
    benchmark's check program, lowered for the described chip.  The call
    wrappers are module-level ``jit``s, so a body is traced once a
    process and lowered once a USE: ``hc_read_fwd`` twice a run (the
    forward pass, and the rematerialised forward, which also hands out
    what its backward needs) for a layer's two blocks, ``hc_write_fwd``
    twice and each backward kernel ONCE for both runs and all their
    blocks; the check program each forward kernel once for its two
    forward passes.  The rounds are a loop inside the bodies, never 20
    copies."""
    from benchmark.loops import train
    from ray_tpu.ops import streams

    cfg = _xing4_cfg(num_layers=2, leading_dense=1)
    assert len(cfg.kind_runs) == 2
    opt = default_optimizer()
    state = _state_shapes(cfg, opt, one_chip)
    tokens = _shape((1, 8193), jnp.int32, one_chip)
    step = _lowered_step(cfg, 1, tokens.shape[1] - 1, one_chip).as_text()
    check = jax.jit(train.program_check(cfg, None)).lower(
        state.params, tokens).as_text()

    def kernels(text):
        return tuple(text.count(f'kernel_name = "{name}"')
                     for name in _HC_KERNELS)

    assert kernels(step) == (4, 2, 1, 1)
    assert kernels(check) == (1, 1, 0, 0)
    # a round's divisions appear once in a body, not once a round
    xs = jax.ShapeDtypeStruct((256, 4 * 128), jnp.bfloat16)
    plan = streams.plan_for(xs, 4, norm_eps=1e-6, clamp=(-30.0, 30.0),
                            iters=20, eps=1e-6, form="kernels")
    small = jax.ShapeDtypeStruct((256, 128), jnp.float32)
    row = jax.ShapeDtypeStruct((1, 128), jnp.float32)
    fwd = str(jax.make_jaxpr(functools.partial(
        streams._read_fwd_call, plan=plan))(
            xs, jax.ShapeDtypeStruct((512, 128), jnp.bfloat16), row, row))
    bwd = str(jax.make_jaxpr(functools.partial(
        streams._read_bwd_call, plan=plan))(
            xs, xs, jax.ShapeDtypeStruct((256, 128), jnp.bfloat16), small,
            small, jax.ShapeDtypeStruct((128, 512), jnp.bfloat16), row, row))
    assert 8 <= fwd.count(" div ") < 20 and 8 <= bwd.count(" div ") < 40


def _olmo_hybrid_cfg(num_layers):
    """Olmo-Hybrid-7B as ``olmohybrid-train-1seq`` runs it (the
    benchmark's configuration file: hidden 3840, 30 delta-rule heads with
    keys of 96 and values of 192, the norm on what a block adds), the
    vocabulary cut, the first ``num_layers`` of its pattern."""
    import dataclasses

    cfg = _benchmark_cfg("olmo-hybrid-7b-d4")
    assert (cfg.embed_dim, cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim,
            cfg.block_norm) == (3840, 30, 96, 192, "output")
    return dataclasses.replace(cfg, vocab_size=4096, num_layers=num_layers)


_DELTA_KERNELS = ("delta_fwd", "delta_bwd")


def test_olmo_hybrid_linear_layer_train_step_compiles(one_chip, as_on_chip):
    """One gated delta-rule layer of Olmo-Hybrid-7B at 4096 positions as a
    train step, WITH the ``delta_*`` kernels (``ops/delta.py``: a head's
    pairs of 64-token chunks as (128, 128) matrices, tiles of (96, 128)
    keys and (192, 128) values with the tokens in the lanes stood up in
    VMEM, products that contract 96 of 128 lanes, the (96, 192) float32
    state in a VMEM scratch, ten float32 products at full precision a
    pair): what Mosaic could refuse — and what the chip's compiler makes
    of the step must fit beside a layer's state."""
    cfg = _olmo_hybrid_cfg(1)
    assert cfg.layer_runs == (("linear_attention", 1),)
    compiled = _compiled_step(cfg, 1, 4096, one_chip)
    text = compiled.as_text()
    assert all(name in text for name in _DELTA_KERNELS)
    assert "gdn_scan" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 6e9


def test_olmo_hybrid_programs_lower_the_delta_kernels_once_a_use(
        one_chip, as_on_chip):
    """The structural guard of the cell's set-up budget, as granite's is:
    Olmo-Hybrid's step program over one period of its pattern (a run of
    three linear layers and the full-attention layer) and the benchmark's
    check program, lowered for the described chip.  The call wrappers are
    module-level ``jit``s, so a body is traced once a process and lowered
    once a USE: the step program holds ``delta_fwd`` twice (the run's
    forward pass, and its rematerialised forward, whose entering states
    and inverses the backward reads) and ``delta_bwd`` once; the check
    program the forward kernel twice, once in the layer scan of each of
    its two forward passes.  Chunks and heads are grid axes and loops: no
    body is a copy a chunk."""
    from benchmark.loops import train

    cfg = _olmo_hybrid_cfg(4)
    assert cfg.layer_runs == (("linear_attention", 3), ("full_attention", 1))
    opt = default_optimizer()
    state = _state_shapes(cfg, opt, one_chip)
    tokens = _shape((1, 4097), jnp.int32, one_chip)
    step = _lowered_step(cfg, 1, tokens.shape[1] - 1, one_chip).as_text()
    check = jax.jit(train.program_check(cfg, None)).lower(
        state.params, tokens).as_text()

    def kernels(text):
        return tuple(text.count(f'kernel_name = "{name}"')
                     for name in _DELTA_KERNELS)

    assert kernels(step) == (2, 1)
    assert kernels(check) == (2, 0)
    # a pair's ten float32 products are in the body once, not once a chunk
    from ray_tpu.ops import delta

    qk = jax.ShapeDtypeStruct((1, 30, 96, 4096), jnp.bfloat16)
    body = str(jax.make_jaxpr(functools.partial(
        delta._fwd_call, interpret=False))(
            qk, qk, jax.ShapeDtypeStruct((1, 30, 192, 4096), jnp.bfloat16),
            jax.ShapeDtypeStruct((1, 30, 2, 4096), jnp.float32),
            jax.ShapeDtypeStruct((1, 30, 96, 192), jnp.float32)))
    assert body.count("Precision.HIGHEST") == 2 * 10


def _kimi_linear_cfg(num_layers):
    """Kimi-Linear-48B-A3B as ``kimilinear-train-s8192`` runs it (the
    benchmark's configuration file: hidden 2304, 32 KDA heads of 128 / 128,
    latent attention at 192 / 128 with no q rank), the vocabulary cut, the
    first ``num_layers`` of its lists."""
    import dataclasses

    cfg = _benchmark_cfg("kimi-linear-48b-a3b-1of16")
    assert (cfg.embed_dim, cfg.kda_heads, cfg.kda_head_dim, cfg.q_lora_rank,
            cfg.position_embedding) == (2304, 32, 128, None, "nope")
    return dataclasses.replace(cfg, vocab_size=4096, num_layers=num_layers)


_KDA_KERNELS = ("kdarule_fwd", "kdarule_bwd")


def test_the_kda_kernels_compile_at_the_published_heads(one_chip, as_on_chip):
    """``ops/delta.py``'s KDA pair at 128 / 128 and 8192 tokens, forward
    and backward: what Mosaic could refuse — tiles of (128, 128) with the
    tokens in the lanes stood up in VMEM, the doubling scan of the
    log-decays along the sublanes (rolls inside the (8, 128) tiles with
    selects on a bit of the row index, a block's row broadcast down whole
    tiles), concatenations of two operands' rows, ten float32 products at
    full precision, two statistics.  And the static count of what the scan
    took off the MXU: a 0/1 sum of a three-part bfloat16 split is a product
    384 lanes wide, NONE in the forward's body and ONE in the backward's
    (``dg``) where PR 63's parent held 12 and 24 (13 apart: the backward
    makes the levels' operands twice); the inverse's ten float32 products
    stand as the parent's bodies hold them, once in the forward's (a product
    prints its precision twice) and not in the backward's, which reads the
    inverse.  SINCE PR 65 the backward's body starts with a sweep of the
    grid step's chunk states (``_kda_sweep``): it holds the doubling scan
    twice (28 rolls for the forward's 14) and TWO products more than it
    held (43 for 41: the two halves of ``k_end^T u``; ``T vb``, ``T kb``
    and ``w H`` moved from the walk into the sweep), none of them at full
    precision or of a split; the forward's 25 products stand."""
    from ray_tpu.ops import delta

    qkv = _shape((1, 8192, 32, 128), jnp.bfloat16, one_chip)

    def summed(q, k, v, g, beta):
        o, _, peak, low = delta.kda_kernels(q, k, v, g, beta)
        return o.astype(jnp.float32).sum() + peak + low

    lowered = jax.jit(jax.grad(summed, argnums=range(5))).lower(
        qkv, qkv, qkv, _shape((1, 8192, 32, 128), jnp.float32, one_chip),
        _shape((1, 8192, 32), jnp.float32, one_chip))
    text = lowered.as_text()
    assert [text.count(f'kernel_name = "{n}"') for n in _KDA_KERNELS] == [
        1, 1]
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
    head = jax.ShapeDtypeStruct((1, 32, 128, 8192), jnp.bfloat16)
    g = jax.ShapeDtypeStruct(head.shape, jnp.float32)
    beta = jax.ShapeDtypeStruct((1, 32, 1, 8192), jnp.float32)
    h0 = jax.ShapeDtypeStruct((1, 32, 128, 128), jnp.float32)
    fwd = str(jax.make_jaxpr(functools.partial(
        delta._kda_fwd_call, interpret=False))(head, head, head, g, beta, h0))
    bwd = str(jax.make_jaxpr(functools.partial(
        delta._kda_bwd_call, interpret=False))(
            head, head, head, g, beta,
            jax.ShapeDtypeStruct((1, 32, 16, 128, 128), jnp.float32),
            jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16), head, h0))
    sum01 = "f32[128,384] = dot_general"
    assert (fwd.count(sum01), bwd.count(sum01)) == (0, 1)
    assert (fwd.count("Precision.HIGHEST"),
            bwd.count("Precision.HIGHEST")) == (2 * 10, 0)
    assert (fwd.count("= dot_general"), bwd.count("= dot_general")) == (
        25, 43)
    assert (fwd.count("roll"), bwd.count("roll")) == (14, 28)


def test_kimi_linear_kda_layer_train_step_compiles(one_chip, as_on_chip):
    """The first layer of Kimi-Linear (a KDA mixer and the dense FFN of
    9216) at 8192 positions as a train step, WITH the ``kdarule_*``
    kernels, under the layer checkpoint: the step holds the forward kernel
    ONCE and the backward once (since PR 65 the checkpoint keeps the
    kernel's output, the pairs' inverses and a state a grid step by name,
    so the rematerialised pass runs no ``kdarule_fwd``), the ``kda_*``
    scopes are on its ops, and what the chip's compiler makes of it fits
    beside a layer's state."""
    cfg = _kimi_linear_cfg(1)
    assert cfg.kind_runs == ((("kda", "dense"), 1),)
    text = _lowered_step(cfg, 1, 8192, one_chip).as_text()
    assert [text.count(f'kernel_name = "{n}"') for n in _KDA_KERNELS] == [
        1, 1]
    compiled = _compiled_step(cfg, 1, 8192, one_chip)   # the pair's case too
    hlo = compiled.as_text()
    assert all(name in hlo for name in _KDA_KERNELS) and "kda_scan" in hlo
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 6e9


def _solar_open2_step(one_chip):
    """``(cfg, compiled step)`` of ``solaropen2-train-s4096`` as the cell
    runs it: the benchmark's configuration file at 1 x 4096, for the
    described chip (a minute and a half, once a process: two tests read
    it)."""
    cfg = _benchmark_cfg("solar-open2-250b-1of32")
    return cfg, _compiled_step(cfg, 1, 4096, one_chip)


def test_the_solar_open2_cells_step_program_fits_the_chip(one_chip,
                                                           as_on_chip):
    """``solaropen2-train-s4096``'s WHOLE step as the cell runs it — the
    benchmark's configuration file (G K K K at hidden 4096, three KDA
    layers of 64 heads x 128 with ``beta`` to 2, 10 of 320 experts held,
    24576 rows) at 1 x 4096 — compiled for the described chip: the peak of
    live bytes the compiler itself reports (``peak_memory_in_bytes``; NOT
    arguments + temporaries, which doubles the arena's packing and reads
    18.20 GB) stays inside the chip's 16.91 GB — 15.12 at PR 64 —, so a
    later change that pushes the fullest KDA cell over the chip fails here
    before the driver's run; the softmax layer runs the flash kernels, the
    KDA layers ``kdarule_*`` and the experts ``moe_gmm*``."""
    cfg, compiled = _solar_open2_step(one_chip)
    assert cfg.kind_runs == ((("attention", "moe"), 1), (("kda", "moe"), 3))
    assert (cfg.embed_dim, cfg.kda_inner, cfg.kda_neg_eigval,
            cfg.local_experts, cfg.vocab_size) == (4096, 8192, True, 10,
                                                   24576)
    hlo = compiled.as_text()
    for kernel in (*_KDA_KERNELS, "flash_fwd", "flash_dkv", "moe_gmm_swiglu"):
        assert kernel in hlo, kernel
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(8.526e9, rel=1e-3)
    assert 0.25 * 16.909e9 < mem.peak_memory_in_bytes < 16.909e9


def test_the_laguna_cells_step_program_fits_the_chip(one_chip, as_on_chip):
    """``laguna-train-s16384``'s WHOLE step as the cell runs it — the
    benchmark's configuration file (F S S S twice at hidden 2048, 32 of 256
    experts of 512 held, 12544 rows) at 1 x 16384 — compiled for the
    described chip: the chip's compiler takes a model whose query heads
    follow the layer's KIND (flash calls of 48 heads on 8 KV heads in the
    full layers and of 64 on 8 under the window, each head a lane block
    read in place), rotates 64 of a head's 128 dimensions in the full
    layers on the 4-D view (scope ``rope_partial``; no ``rope_*`` kernel
    call stands under it) and the whole head in the sliding ones by the
    rotation's kernel, gates a head by one number (``attn_head_gate``),
    and the peak of live bytes it reports stays inside the chip's 16.91 GB
    — 14.79 at PR 83 — and over a quarter of it."""
    cfg = _benchmark_cfg("laguna-xs.2-33b-a3b-1of8")
    S, F = "sliding_attention", "full_attention"
    assert cfg.kind_runs == (((F, "dense"), 1), ((S, "moe"), 3),
                             ((F, "moe"), 1), ((S, "moe"), 3))
    assert (cfg.q_heads(False), cfg.q_heads(True), cfg.num_kv_heads,
            cfg.rotary_dim(False), cfg.rotary_dim(True)) == (48, 64, 8, 64,
                                                             128)
    compiled = _compiled_step(cfg, 1, 16384, one_chip)
    hlo = compiled.as_text()
    for kernel in ("flash_fwd_win", "flash_dkv_win", "flash_fwd", "flash_dkv",
                   "rope_fwd", "rope_bwd", "moe_gmm_swiglu"):
        assert kernel in hlo, kernel
    assert "flash_dq" not in hlo
    # q as the flash kernels take it in each kind of layer, heads side by
    # side (a full layer's 6144 columns, a sliding layer's 8192)
    calls = [line for line in hlo.splitlines()
             if "custom-call" in line and "/flash_" in line]
    assert any("bf16[1,16384,6144]" in c for c in calls)
    assert any("bf16[1,16384,8192]" in c for c in calls)
    # the partial rotation is XLA's: its ops carry the scope, no kernel does
    partial = [line for line in hlo.splitlines() if "/rope_partial/" in line]
    assert partial and not [p for p in partial if " custom-call(" in p]
    assert "/attn_out/attn_head_gate/" in hlo
    # the kernel rotates the sliding runs' q and k alone: 2 runs x (q, k)
    assert len(re.findall(
        r"custom-call\(.*/rope/jit\(_call\)/rope_fwd/pallas_call\"", hlo)) \
        == 2 * 2 * 2    # ... forward and under the checkpoint
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(6.710e9, rel=1e-3)
    assert 0.25 * 16.909e9 < mem.peak_memory_in_bytes < 16.909e9


def test_a_share_layers_row_buffers_are_made_in_the_layer_loop_and_not_copied(
        one_chip, as_on_chip):
    """The same compiled step: the static row buffers of a share's expert
    layers (``ops/moe.py::_row_buffer``, 32768 rows x 4096 here, 268 MB)
    are made by the layer that fills them.  Every ``moe_row_buffer`` call
    takes an operand and keeps the layer loop in its ``op_name`` — a call
    that depends on nothing was lifted out of the forward loop of the run
    of three layers when the scan was differentiated (``jit(step)/
    moe_dispatch/...``, no ``while/body``) —, the run's forward and
    backward loops hold their own calls, and NO ``copy`` of the buffer's
    shape is left: a lifted buffer rode through the loop as a constant and
    every layer copied it whole before writing a share of it (PERF.md §6,
    PR 75)."""
    hlo = _solar_open2_step(one_chip)[1].as_text()
    buffer = "bf16[32768,4096]"
    calls = [line for line in hlo.splitlines()
             if re.match(r"\s*%?moe_row_buffer[\w.]* = ", line)]
    # forward 2 a layer; backward the dispatch's rerun, the combine's
    # gradient and the dispatch's gradient's sum: inlined for the run of
    # one layer, once each in the loops of the run of three
    assert len(calls) == 2 * (2 + 3)
    for line in calls:
        assert f" {buffer}" in line
        assert re.search(r"custom-call\(%[\w.\-]+\)", line), line[:200]
        assert "while/body" in re.search(r'op_name="([^"]*)"', line).group(1)
    assert not [line[:200] for line in hlo.splitlines()
                if re.match(rf"\s*%?[\w.\-]+ = {re.escape(buffer)}\S* copy\(",
                            line)]


def test_the_selection_kernels_compile_at_the_published_widths(one_chip,
                                                               as_on_chip):
    """What an ``indexed`` layer of ``keyevl2-train-s16384`` runs between its
    projections (``blocks/attention.py::_selected_attention``: 32 / 4 heads
    x 128, a 16 x 64 indexer against one key), forward and backward, at
    4096 tokens and 512 keys a query so that the compile stays short: Mosaic
    takes every kernel of the selection — the index scores and their
    gradient, the bit-counting top-k, ``sparse_mask`` (a compare stored as
    an int8 tile, a float32 tile turned round in VMEM), the flash kernels
    under an int8 mask tile and its transpose, the indexer's KL — and
    between the top-k and the flash kernels XLA touches no ``(s, s)`` array:
    the compiled text holds no ``transpose`` and nothing outside the kernels
    writes an int8 ``(1, s, s)``.  ``sparse_select`` ALONE also at the
    cell's own ``(1, 16384, 16384)``, 2048 keys a query: a count's loop over
    the chunks of 2048 columns a block of rows can select from, its trip
    count off the grid step, the ordered keys in 2 MB of VMEM scratch, the
    tie's walk under a predicate reduced from the block's counts.  The
    WHOLE step at 1 x 16384
    (a minute and a half to compile: 5.12 + 10.47 GB, peak 12.57 of the
    chip's 16.91, PR 70) is ``benchmark/rehearse_compile.py``'s by hand."""
    from ray_tpu.models.blocks import attention as block

    cfg = dataclasses.replace(
        _benchmark_cfg("keye-vl-2.0-30b-a3b-1of8"), sa_config={
            "indexer_num_heads": 16, "indexer_head_dim": 64,
            "indexer_num_kv_heads": 1, "topk": 512})
    s, bf = 4096, jnp.bfloat16
    shapes = [(1, s, 32, 128), (1, s, 4, 128), (1, s, 4, 128),
              (1, s, 16, 64), (1, s, 64), (1, s, 16)]
    operands = [_shape(shape, jnp.float32 if i == 5 else bf, one_chip)
                for i, shape in enumerate(shapes)]

    def loss(*args):
        o, kl, _, _ = block._selected_attention(cfg, False, *args)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(kl)

    hlo = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        *operands).compile().as_text()
    for kernel in ("sparse_scores", "sparse_scores_bwd", "sparse_select",
                   "sparse_mask", "flash_fwd_dsa", "flash_dkv_dsa",
                   "sparse_loss"):
        assert kernel in hlo, kernel
    assert not re.search(r"[}\]] transpose\(", hlo)
    masks = [line.split(", metadata=")[0][:400] for line in hlo.splitlines()
             if re.search(rf" = \(?s8\[1,{s},{s}\]", line)]
    assert masks and all(re.search(
        r"custom-call\(|get-tuple-element\(%sparse_mask", line)
        for line in masks), masks
    from ray_tpu.ops import sparse_attention

    assert sparse_attention._select_chunk(16384) == 2048
    alone = jax.jit(functools.partial(
        sparse_attention.select, topk=2048, interpret=False)).lower(
            _shape((1, 16384, 16384), jnp.float32, one_chip)).compile()
    assert "sparse_select" in alone.as_text()


@pytest.mark.parametrize("shape,biased,tokens_last", [
    ((1, 8192, 12288), False, True),   # Kimi-Linear's q, k, v
    ((1, 4096, 24576), False, True),   # Solar-Open2's
    ((1, 4096, 11520), False, True),   # Olmo-Hybrid's: 480 channels a tile
    ((2, 8192, 6144), True, False),    # Nemotron-H's x, B, C
    ((1, 8192, 4352), True, False),    # granite's: 34 lane tiles
    ((1, 4096, 10240), True, False),   # Nemotron-3 Super's
], ids=["kimilinear", "solaropen2", "olmohybrid", "nemotronh", "granite4h",
        "nemotron3super"])
def test_the_convolutions_pair_compiles_at_the_cells_shapes(
        one_chip, as_on_chip, shape, biased, tokens_last):
    """``causal_conv1d`` at the six cells' ``(b, s, c)``, value and the
    gradients to x, weight and bias, the array standing as each cell's
    mixer has it (the delta rules' tokens-last): the kernels
    ``causal_conv_fwd`` and ``causal_conv_bwd`` — rotates of a float32
    tile along its sublanes or its lanes, a second block of the same
    array one hardware tile long before and after a tile at a clamped
    index, the weight's gradient in an output block (a column a tap where
    the tokens are along the lanes) that stays over the grid's two inner
    axes: what Mosaic could refuse."""
    from ray_tpu.ops.ssm import causal_conv1d

    def f(x, w, bias, dy):
        return (causal_conv1d(x, w, bias, tokens_last=tokens_last
                              ).astype(jnp.float32) * dy).sum()

    x = _shape(shape, jnp.bfloat16, one_chip)
    bias = _shape(shape[2:], jnp.float32, one_chip) if biased else None
    text = jax.jit(jax.value_and_grad(
        f, argnums=(0, 1, 2) if biased else (0, 1))).lower(
            x, _shape((4, shape[2]), jnp.float32, one_chip), bias, x
        ).compile().as_text()
    assert "causal_conv_fwd" in text and "causal_conv_bwd" in text


def _written(text):
    """The instructions of a compiled text that write an array to memory:
    every line outside the fused computations, whose ops live in
    registers."""
    fused = False
    for line in text.splitlines():
        start = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if start:
            fused = start.group(1).startswith("fused_computation")
        elif not fused and " = " in line:
            yield line


@pytest.mark.parametrize("mixer", ["kda", "mamba"])
def test_the_mixers_convolution_is_the_pair_and_writes_no_float32_copy(
        one_chip, as_on_chip, mixer):
    """Kimi-Linear's first layer (a KDA mixer; ONE layer, the step
    ``test_kimi_linear_kda_layer_train_step_compiles`` compiled: the
    two-layer step compiles for 50 s and this file is the suite's longest) and two
    layers of granite (Mamba-2) at their published widths and 8192
    positions, as the one-chip train step, compiled: each scanned run of
    layers holds the convolution's forward
    kernel twice (the pass and its rerun under the layer checkpoint) and
    the backward kernel once, and under ``kda_conv`` / ``ssm_conv`` no op
    writes a float32 ``(b, s, c)`` array — the XLA form's backward pass
    read two (``dpre``'s operands) and remade ``pre``, its sigmoid and
    ``dpre`` in float32 at every tap of every element (PERF.md §6,
    PR 67)."""
    if mixer == "kda":
        cfg, scope = _kimi_linear_cfg(1), "kda_conv"
        channels = 3 * cfg.kda_inner
    else:
        cfg, scope = _granite_cfg(("mamba", "mamba")), "ssm_conv"
        channels = cfg.ssm_conv_dim
    assert {kind[0] for kind, _ in cfg.kind_runs} == {mixer}
    runs = len(cfg.kind_runs)
    text = _compiled_step(cfg, 1, 8192, one_chip).as_text()
    for kernel, calls in (("causal_conv_fwd", 2), ("causal_conv_bwd", 1)):
        assert len(re.findall(
            rf"custom-call\(.*/{scope}/.*/{kernel}/pallas_call\"", text)
        ) == calls * runs, kernel
    wide = [line for line in _written(text)
            if re.search(rf"= f32\[1,8192,{channels}\]", line)
            and f"/{scope}/" in line]
    assert not wide, wide[:3]


def test_lfm2_conv_layer_train_step_compiles(one_chip, as_on_chip):
    """One gated short-convolution layer with its expert FFN of
    LFM2-8B-A1B as ``lfm2moe-train-s8192`` runs it (the benchmark's
    configuration file: hidden 2048, 3 taps, experts of 1792 with 16 of
    32 held), the vocabulary cut, at 8192 positions as a train step: the
    convolution is plain XLA (shifted slices of a (8192, 6144) array),
    the grouped kernels are Mosaic's at the new shapes (column blocks of
    1792, half the rows live); what the chip's compiler makes of both
    must fit beside the layer's state.  (The attention layers' flash
    kernels at head 64 are granite's case above.)"""
    import dataclasses

    cfg = _benchmark_cfg("lfm2-8b-a1b-1of2")
    assert (cfg.embed_dim, cfg.sconv_width, cfg.head_dim, cfg.qk_head_norm,
            cfg.mlp_dim, cfg.experts_held, cfg.num_experts) == (
                2048, 3, 64, True, 1792, 16, 32)
    cfg = dataclasses.replace(cfg, vocab_size=4096, num_layers=1,
                              leading_dense=0, layer_types=("conv",))
    assert cfg.kind_runs == ((("conv", "moe"), 1),)
    compiled = _compiled_step(cfg, 1, 8192, one_chip)
    assert _has_kernel(compiled)
    text = compiled.as_text()
    assert "sconv_gate" in text and "moe_experts" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 5e9


# -- RoPE where the projections leave q and k ---------------------------------

def _ops_of(text, opcode, scopes):
    """``(shape with layout, op_name)`` of every ``opcode`` of a compiled
    text whose JAX name stack holds one of ``scopes``."""
    found = []
    for m in re.finditer(
            r"= (\S+) " + opcode + r"\(.*?op_name=\"([^\"]*)\"", text):
        if any(f"/{scope}/" in m.group(2) for scope in scopes):
            found.append(m.groups())
    return found


@pytest.mark.parametrize("shape,d,dtype", [
    ((1, 16384, 4096), 128, jnp.bfloat16),   # Mellum2's q: one row
    ((1, 16384, 512), 128, jnp.bfloat16),    # ... and its k: 4 KV heads
    ((4, 4096, 2048), 256, jnp.bfloat16),    # a head of two lane blocks
    ((1, 8, 128), 128, jnp.float32),         # one sublane tile of one head
], ids=["q-one-row", "k-one-row", "d256", "smallest"])
def test_the_rope_kernel_compiles(one_chip, as_on_chip, shape, d, dtype):
    """``rope_fwd`` with the pre-scale epilogue and ``rope_bwd`` at the
    widths a cell runs and at the edges of ``rotary.fits``: the lane rotate
    of a head's block, the tables' block beside x's."""
    from ray_tpu.ops import rotary

    assert rotary.fits(shape[1], d)
    tables = (_shape((shape[1], d), jnp.float32, one_chip),) * 2
    text = jax.jit(jax.grad(lambda x, c, s: rotary.rope_rotate(
        x, c, s, d, 0.125).astype(jnp.float32).sum())).lower(
            _shape(shape, dtype, one_chip), *tables).compile().as_text()
    assert "rope_fwd" not in text and "rope_bwd" in text   # a linear rule
    assert _has_kernel(jax.jit(lambda x, c, s: rotary.rope_rotate(
        x, c, s, d, 0.125)).lower(
            _shape(shape, dtype, one_chip), *tables).compile())


@pytest.mark.parametrize("config,rows,seq,heads,kv_heads", [
    ("mistral-7b-v0.1-d4", 4, 4096, 32, 8),       # grouped KV, no norm
    ("olmoe-1b-7b-0125-1chip", 4, 4096, 16, 16),  # MHA, a norm over all of q
    ("mistral-7b-v0.1-d4", 1, 4096, 32, 8),       # ONE row, as a cell's check
], ids=["mistral", "olmoe", "one-row"])
def test_rope_on_the_flat_arrays_leaves_no_copy_of_q_or_k(
        one_chip, as_on_chip, config, rows, seq, heads, kv_heads):
    """Two layers (the scan stays a loop) of a cell whose mixer rotates
    ``(b, s, heads x 128)`` before the reshape, the vocabulary cut, as the
    one-chip train step: the rotation is the kernel's (``rope_fwd``,
    ``rope_bwd``: custom calls) and under ``rope`` stands no float32 array
    wider than the tables' ``(s, 128)`` (the XLA form's stood as wide as
    q: PERF.md §6, PR 58); between the projections and ``flash_fwd`` /
    ``flash_dkv`` XLA copies neither q nor k (on the 4-D
    view it laid RoPE's fusion out with the SEQUENCE on the lanes,
    ``{1,3,2,0}``, and copied both into the kernels' ``{2,1,0}`` every
    layer and pass: PERF.md §6, PR 54); and the flash kernels' pre-scale of
    q is the rotation's epilogue: under ``attention`` no multiply is left
    in the forward pass, its rerun or — ``o * do`` being the backward
    kernel's own since PR 77 — the backward pass."""
    import dataclasses

    cfg = _benchmark_cfg(config)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.qk_head_norm) == (heads, kv_heads, 128, False)
    cfg = dataclasses.replace(cfg, num_layers=2, vocab_size=4096)
    text = _compiled_step(cfg, rows, seq, one_chip).as_text()
    assert all(name in text for name in ("flash_fwd", "flash_dkv"))
    assert "flash_dq" not in text
    for kernel, calls in (("rope_fwd", 4), ("rope_bwd", 2)):  # q's and k's
        assert len(re.findall(
            rf"custom-call\(.*/rope/jit\(_call\)/{kernel}/pallas_call\"",
            text)) == calls
    widths = [math.prod(map(int, m.group(1).split(",")))
              for m in re.finditer(
                  r"= f32\[([\d,]+)\]\S* \S+\(.*op_name=\"[^\"]*/rope/", text)]
    assert widths and max(widths) <= seq * 128
    mixer = ("rope", "attn_qkv", "attention")
    q_or_k = re.compile(
        rf"bf16\[{rows},{seq},(?:{heads * 128}|{kv_heads * 128}"
        rf"|{heads},128|{kv_heads},128)\]")
    assert not [c for c in _ops_of(text, "copy", mixer)
                if q_or_k.match(c[0])]
    assert not [f for f in _ops_of(text, "fusion", mixer) + _ops_of(
        text, "copy", mixer) if "{1,3,2,0" in f[0]]
    assert not [name for _, name in _ops_of(text, "multiply", ("attention",))
                if name.endswith("/attention/mul")]


def test_rope_stays_on_the_4d_view_after_a_per_head_norm():
    """What the mixer can see decides the view RoPE works on: a layer with
    ``qk_head_norm`` already holds q and k to ``(b, s, heads, d)`` and
    keeps ``apply_rope`` (rotate-half by slices of a HEAD: a flat form
    after the norm compiled to float32 copies of q, PERF.md §6, PR 54), as
    does a head narrower than the lanes; the same layer at a head of 128
    lanes without the norm rotates ``(b, s, heads x d)`` by the kernel, one
    row or two, with no op that has a head for a dimension."""
    from ray_tpu.models import llama

    def rope_ops(rows, **kw):
        cfg = LlamaConfig.tiny(num_layers=1, **kw)
        params = jax.eval_shape(
            lambda k: llama.init_params(k, cfg), jax.random.PRNGKey(0))
        text = str(jax.make_jaxpr(
            lambda p, t: llama.loss_fn(p, {"tokens": t}, cfg)[0])(
                params, jax.ShapeDtypeStruct((rows, 17), jnp.int32)))
        half_a_head = f"f32[{rows},16,{cfg.num_heads},{cfg.head_dim // 2}]"
        return half_a_head in text, "rope_fwd" in text

    assert rope_ops(2, head_dim=128, qk_head_norm=True) == (True, False)
    assert rope_ops(2) == (True, False)
    assert rope_ops(1, head_dim=128) == (False, True)
    assert rope_ops(2, head_dim=128) == (False, True)


def test_the_selective_scans_kernels_compile_at_the_published_widths(
        one_chip):
    """``selscan_fwd`` / ``selscan_bwd`` (``ops/ssm.py``) at what a Mamba-1
    layer of ``phi4flash-train-s16384`` hands them: 16384 tokens of 5120
    channels as ``(b, s, 40, 128)`` float32, 16 state numbers a channel, the
    chunks' scalars in SMEM; the backward's three ``(chunk, n, 8, 128)``
    arrays in VMEM under its limit."""
    from ray_tpu.ops import ssm

    batch, s, channels, n = 1, 16384, 5120, 16
    rows, chunks = channels // 128, s // ssm._SEL_CHUNK
    f32 = functools.partial(_shape, dtype=jnp.float32, sharding=one_chip)
    tokens, a3, d2 = f32((batch, s, rows, 128)), f32((n, rows, 128)), f32(
        (rows, 128))
    scalars = f32((batch, chunks, 1, ssm._SEL_CHUNK * n))
    states = f32((batch, chunks, n, rows, 128))
    fwd = jax.jit(functools.partial(ssm._sel_fwd_call, interpret=False)
                  ).lower(tokens, tokens, a3, scalars, scalars, d2).compile()
    bwd = jax.jit(functools.partial(ssm._sel_bwd_call, interpret=False)
                  ).lower(tokens, tokens, a3, scalars, scalars, d2, states,
                          tokens).compile()
    assert "selscan_fwd" in fwd.as_text() and _has_kernel(fwd)
    assert "selscan_bwd" in bwd.as_text() and _has_kernel(bwd)
    assert ssm.selscan_kernels_fit(channels)
