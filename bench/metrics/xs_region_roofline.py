"""Share of the bandwidth roofline that XSBench's lookup region reaches:
the least bytes the window's lookups must read (``bench/counts/xsbench``)
over the chip's HBM bandwidth, divided by the device time of the
simulation programs in the trace.  Nothing to read without them."""
from bench.counts import xsbench as counts


def read(ctx):
    lo, hi = ctx["window"]
    region_s = sum(s for name, s in ctx["trace"].module_seconds(lo, hi).items()
                   if name.startswith("jit_run_event_simulation"))
    if region_s <= 0:
        return None
    need_s = (counts.bytes_per_lookup(ctx["config"]) * ctx["stats"]["lookups"]
              / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * need_s / region_s
