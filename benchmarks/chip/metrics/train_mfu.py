"""Model FLOP utilization of the traced window: model FLOPs per token (no
recompute, from the configuration's published shapes) times the tokens the
traced window trained per second, over the chip's bf16 peak."""
from chiplib.flops import llama_train_flops_per_token


def read(r):
    steps = r.facts.get("steps")
    if not steps:
        return None
    w = r.workload
    tokens = steps * w["batch"] * w["seq"]
    flops = llama_train_flops_per_token(r.config, w["seq"]) * tokens
    return 100.0 * flops / r.trace.window_s / r.peak["bf16_flops_per_s"]
