"""Roofline share of the ``ce_proxy`` Pallas kernel: the least time its
calls in the window need (``flops.ce_proxy_cost`` over the bf16 peak, or its
bytes over HBM bandwidth, whichever is larger) over the device time of its
operations in the trace.  The kernel multiplies fp32 operands in bf16
passes; bf16 is the only floating-point peak published for the chip."""
from chipbench import flops, trace
from chipbench.drivers import lm_common

KERNEL = r"^ce_proxy_pallas"


def read(ctx):
    rec, cell, tr, pk = ctx["record"], ctx["cell"], ctx["trace"], ctx["peaks"]
    t = trace.kernel_s(tr, KERNEL)
    if t <= 0 or not rec.get("refreshes"):
        return None
    _, hf = lm_common.program_config(cell)
    tokens = rec["docs"] * int(cell.param("seq_len"))
    f, b = flops.ce_proxy_cost(tokens, int(hf["hidden_size"]), int(hf["vocab_size"]))
    least, _ = flops.least_time_s(f, b, pk["bf16_flops"], pk["hbm_bytes_per_s"])
    return 100.0 * least / t
