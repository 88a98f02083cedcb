"""Model FLOP/s utilisation of a mixture-of-experts cell:
``train_tokens_per_s`` (this run's window, host clock) x the operations
forward and backward REQUIRE per token with ``num_experts_per_tok`` experts
and the router (``benchmark/flops_moe.py``) over chips x the published
bf16 peak.  ``train_step.mfu_pct`` counts one expert a token here."""

from benchmark import flops_moe


def read(run):
    if "num_experts_per_tok" not in run["conf"]:
        return None
    per_token = flops_moe.train_flops_per_token(run["conf"],
                                                run["job"]["seq"])
    return (100.0 * run["end_to_end"]["train_tokens_per_s"] * per_token
            / (run["chips"] * run["peak"]["bf16_flops_per_s"]))
