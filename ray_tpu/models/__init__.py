"""ray_tpu.models — model families shipped with the framework.

The reference ships model zoos inside RLlib (``rllib/models/``, torch/tf
nets + 299-LoC JAX stubs, SURVEY.md §2.4); the TPU build makes the flagship
an LLM family designed for mesh parallelism from the start.

``llama.py`` is the decoder (configuration, parameters, the scan over
layers, head, loss); ``blocks/`` holds what a layer is composed of, one
module a mixer or FFN (``blocks.MIXERS``, ``blocks.FFNS``) and the residual.
"""

from ray_tpu.models.llama import (
    LlamaConfig,
    init_params,
    param_logical_axes,
    forward,
    loss_fn,
)

__all__ = ["LlamaConfig", "init_params", "param_logical_axes", "forward",
           "loss_fn"]
