"""Sharded train-step construction: params + optimizer on a mesh.

The reference's analog is the torch training loop the user writes inside
``train_loop_per_worker`` plus DDP wrapping (``prepare_model``,
``python/ray/train/torch/train_loop_utils.py:75``).  Here the framework owns
the step: loss -> grad -> optax update, jitted once over the global mesh;
XLA inserts the gradient psum (dp), reduce-scatter/all-gather (fsdp), and
layer collectives (tp/sp/ep) from the sharding annotations.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models.blocks import layer_scopes
from ray_tpu.models.llama import (
    LlamaConfig, forward_pipelined, init_params, loss_and_counts,
    param_logical_axes, update_router_bias,
)
from ray_tpu.parallel.mesh import AXIS_DP, AXIS_FSDP, AXIS_PP
from ray_tpu.parallel.sharding import (
    LogicalAxisRules, named_sharding, sharding_tree,
)
from ray_tpu.util import tracing

# A process that builds train steps has JAX: from here on (in a worker
# granted chips: since ``train/backend.py::bring_up`` opened them) each
# program and collector pause is a span (``tracing.watch_process``).
tracing.watch_process()


# The ``jax.named_scope`` names that between them cover the step program,
# with no overlap: the decoder's own, those the registered blocks declare
# (``models/blocks``) and ``optimizer``, which ``step`` below opens.  A
# device op's ``op_name`` carries exactly one of them, wrapped by JAX in the
# phase: bare or ``jvp(..)`` is the forward pass, under
# ``rematted_computation`` the rematerialised forward (of all but the
# residuals a block keeps by name), ``transpose(jvp(..))`` the backward
# pass (``util.tracing.step_breakdown``).
STEP_SCOPES = ("embed", *layer_scopes(), "mtp_in", "bd_noise", "ut_exit",
               "lm_head", "loss", "optimizer")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any


def _fresh_state(key: jax.Array, cfg: LlamaConfig,
                 optimizer: optax.GradientTransformation) -> TrainState:
    params = init_params(key, cfg)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=optimizer.init(params))


def train_state_shardings(cfg: LlamaConfig,
                          optimizer: optax.GradientTransformation, mesh,
                          rules: Optional[LogicalAxisRules] = None
                          ) -> TrainState:
    """Where every leaf of the train state lives on ``mesh``: parameters
    by their logical axes; an optimizer-state leaf whose tree path ends
    in a parameter's path (adam's mu/nu mirror the parameter tree) like
    that parameter; everything else (step, counts) replicated."""
    params = sharding_tree(param_logical_axes(cfg), mesh, rules)
    replicated = NamedSharding(mesh, P())
    by_path = dict(jax.tree_util.tree_leaves_with_path(params))

    def mirror(path, _):
        for n in range(len(path)):  # longest suffix first
            if path[n:] in by_path:
                return by_path[path[n:]]
        return replicated

    shapes = jax.eval_shape(lambda k: _fresh_state(k, cfg, optimizer),
                            jax.random.PRNGKey(0))
    return TrainState(
        step=replicated, params=params,
        opt_state=jax.tree_util.tree_map_with_path(mirror,
                                                   shapes.opt_state))


def init_train_state(key: jax.Array, cfg: LlamaConfig,
                     optimizer: optax.GradientTransformation,
                     mesh=None,
                     rules: Optional[LogicalAxisRules] = None) -> TrainState:
    """Init params and optimizer state, sharded onto ``mesh``.

    One jitted program with explicit ``out_shardings``: every leaf is
    BORN where it lives.  Left to itself the whole model is built on the
    default device before resharding, and ``jit(optimizer.init)`` puts
    the (constant) adam moments on that one device too — on a four-chip
    host the first chip then holds four times its share.
    """
    shardings = None if mesh is None else train_state_shardings(
        cfg, optimizer, mesh, rules)
    return jax.jit(lambda k: _fresh_state(k, cfg, optimizer),
                   out_shardings=shardings)(key)


def make_train_step(cfg: LlamaConfig,
                    optimizer: optax.GradientTransformation, *,
                    mesh=None, rules: Optional[LogicalAxisRules] = None,
                    pipelined: bool = False,
                    num_microbatches: int = 1,
                    donate: bool = True
                    ) -> Callable[[TrainState, Dict[str, jax.Array]],
                                  Tuple[TrainState, Dict[str, jax.Array]]]:
    """Build the jitted train step.  Batch: {"tokens": (b, s+1) int32}."""

    def compute_loss(params, batch, step):
        forward_fn = None
        if pipelined:
            forward_fn = lambda p, t: forward_pipelined(
                p, t, cfg, mesh=mesh, num_microbatches=num_microbatches,
                rules=rules)
        return loss_and_counts(params, batch, cfg, mesh=mesh, rules=rules,
                               forward_fn=forward_fn, step=step)

    def step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        # the step's number: what a block-diffusion model's noise is drawn
        # from (fresh every step, the same again in a resumed job)
        (_, (metrics, counts)), grads = jax.value_and_grad(
            compute_loss, has_aux=True)(state.params, batch, state.step)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params)
            params = optax.apply_updates(state.params, updates)
            if counts is not None:
                # a router's selection bias has no gradient: it moves by
                # its own rule, from this step's load, in place of the
                # optimizer's update (which would only decay it)
                params = update_router_bias(state.params, params, counts,
                                            cfg)
            metrics = dict(
                metrics,
                grad_norm=optax.global_norm(grads).astype(jnp.float32))
            return TrainState(step=state.step + 1, params=params,
                              opt_state=opt_state), metrics

    donate_argnums = (0,) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums)


def default_optimizer(lr: float = 3e-4, weight_decay: float = 0.1,
                      warmup: int = 100, decay_steps: int = 10000,
                      grad_clip: float = 1.0) -> optax.GradientTransformation:
    """AdamW + cosine schedule + clipping — the standard LLM recipe."""
    sched = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup, max(decay_steps, warmup + 1))
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(sched, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )
