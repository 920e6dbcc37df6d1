"""Share of its roofline that the latent paged decode-attention kernel
reaches: the least time its calls in the window could take, the larger
of the bytes they must move over the chip's HBM bandwidth and their
operations over its bf16 peak (``bench/counts/mla``; every held layer of
every tick, the active slots' contexts), divided by the kernel's device
time in the trace.  Nothing to read where no op of the kernel is found."""
from bench import trace
from bench.counts import mla as counts

#: the kernel's custom call is named after the function that launches it
KERNEL = "%latent_paged_decode_attention_pallas"


def read(ctx):
    lo, hi = ctx["window"]
    kernel_s = sum(s for name, s in ctx["trace"].op_seconds(lo, hi).items()
                   if trace.op_label(name).startswith(KERNEL))
    if kernel_s <= 0:
        return None
    c, s = ctx["config"], ctx["stats"]
    calls = s["ticks"] * c["num_hidden_layers"]
    slots = int(c["engine"]["batch_slots"])
    ctx_sum = s["context_sum"] * c["num_hidden_layers"]
    need = counts.kernel_bytes([ctx_sum], slots * calls, c)
    work = counts.kernel_flops([ctx_sum], c)
    p = ctx["peaks"]
    bound = max(need / p["hbm_bytes_per_s"], work / p["bf16_flops"])
    return 100.0 * bound / kernel_s
