"""The paged-attention kernel's share of its roofline: the least time
the chip needs for the operations and bytes of the window's decoded
tokens (pooled K/V each reads, from bench.flops) over the kernel's
device time in the trace."""
from bench import flops, peaks, window


def read(ctx):
    run, tr = ctx["run"], ctx["trace"]
    t = tr.time_of(window.KERNEL, ops=True)
    toks = window.decoded(run)
    if t is None or not toks:
        return None
    ops, nbytes = flops.paged_attention_call(
        run.sizes, [p + j - 2 for p, j in toks])
    layers = run.sizes["layers"]
    pk = peaks.peaks(run.dev.device_kind)
    least = max(ops * layers / pk["bf16_flops"],
                nbytes * layers / pk["hbm_bytes_per_s"])
    return 100.0 * least / t
