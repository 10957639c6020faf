"""Model FLOP utilization of the traced window for a DeepSeek-V2 cell: model
FLOPs per token (``chiplib/mla_moe_flops.py``: no recompute, no capacity
padding) times the tokens the traced window trained per second, over the
chip's bf16 peak.  There is no kernel of its own: the whole step's share."""
from chiplib.mla_moe_flops import mla_moe_train_flops_per_token


def read(r):
    steps = r.facts.get("steps")
    if not steps or r.trace is None:
        return None
    w = r.workload
    tokens = steps * w["batch"] * w["seq"]
    flops = mla_moe_train_flops_per_token(r.config, w["seq"]) * tokens
    return 100.0 * flops / r.trace.window_s / r.peak["bf16_flops_per_s"]
