"""Model FLOP/s utilisation: ``train_tokens_per_s`` (this run's window,
host clock) x the operations forward and backward REQUIRE per token (the
FLOP module the configuration names, ``benchmark/flops.py::of``; nothing
recomputed counts) over chips x the published bf16 peak of
``benchmark/peaks.json``."""

from benchmark import flops


def read(run):
    per_token = flops.of(run["conf"]).train_flops_per_token(
        run["conf"], run["job"]["seq"])
    return (100.0 * run["end_to_end"]["train_tokens_per_s"] * per_token
            / (run["chips"] * run["peak"]["bf16_flops_per_s"]))
