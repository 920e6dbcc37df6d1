"""Share of the bandwidth roofline that the paged decode-attention kernel
reaches: the K and V bytes the active slots' contexts need
(``bench/counts/paged_attention``), in every held layer of every tick in
the window, over the chip's HBM bandwidth, divided by the kernel's device
time in the trace.  Nothing to read where no op of the kernel is found."""
from bench import trace
from bench.counts import paged_attention as counts

#: the kernel's custom call is named after the function that launches it
KERNEL = "%paged_decode_attention_pallas"


def read(ctx):
    lo, hi = ctx["window"]
    kernel_s = sum(s for name, s in ctx["trace"].op_seconds(lo, hi).items()
                   if trace.op_label(name).startswith(KERNEL))
    if kernel_s <= 0:
        return None
    c = ctx["config"]
    need = counts.kv_bytes([ctx["stats"]["context_sum"]],
                           c["num_key_value_heads"], c["head_dim"])
    need *= c["num_hidden_layers"]
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / kernel_s
