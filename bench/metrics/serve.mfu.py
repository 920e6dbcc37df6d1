"""Whole-step share of the chip's bf16 peak: the forward operations of the
tokens the engine processed in the window (held layers, attention over
each token's context, and the head; ``bench/counts/qwen2``) per second of
the window, over the peak."""
from bench.counts import qwen2 as counts


def read(ctx):
    s = ctx["stats"]
    if not s.get("tokens_processed"):
        return None
    work = counts.flops(ctx["config"], s["tokens_processed"], s["context_sum"])
    return 100.0 * work / s["elapsed_s"] / ctx["peaks"]["bf16_flops"]
