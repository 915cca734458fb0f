"""Share of its roofline that the Pallas flash-attention forward reaches:
per call, the least time for the causal half of QK^T and PV at the bf16
peak, or for reading q, k, v and writing o in f32, whichever is longer,
over the call's device time; summed over the traced calls. Each call
covers every client's batch of the local step (the engine vmaps the client
axis into one launch)."""
import re

KERNEL = re.compile(r"attention")


def read(ctx):
    from bench.flops.lm import flash_fwd_call
    peak, bw = ctx.peaks.get("bf16_flops_per_s"), ctx.peaks.get(
        "hbm_bytes_per_s")
    got = ctx.kernel_ns_per_round(KERNEL)
    if not peak or got is None:
        return None
    calls, ns = got
    c = ctx.cell
    flops, nbytes = flash_fwd_call(c.config, c.clients * c.batch, c.seq_len)
    least_s = max(flops / peak, nbytes / bw)
    return 100.0 * calls * least_s / (ns * 1e-9)
