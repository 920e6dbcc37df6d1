"""Whole-step share of the chip's bf16 peak: the forward operations of the
tokens the engine processed in the window (the held layers with the
routed experts at their expected evaluations on this chip, latent
attention over each token's context, and the head;
``bench/counts/deepseek_v2``) per second of the window, over the peak."""
from bench.counts import deepseek_v2 as counts


def read(ctx):
    s = ctx["stats"]
    if not s.get("tokens_processed"):
        return None
    work = counts.flops(ctx["config"], s["tokens_processed"], s["context_sum"])
    return 100.0 * work / s["elapsed_s"] / ctx["peaks"]["bf16_flops"]
