"""Model FLOP utilization of the refreshes in the window: the operations
proxy extraction needs for every pool token (the forward trunk and
``ce_proxy``'s 4·D·V, ``flops.lm_extract_flops_per_token``), over the window
times the chip's bf16 peak.  The greedy's few GFLOP are left out."""
from chipbench import flops
from chipbench.drivers import lm_common


def read(ctx):
    rec, cell = ctx["record"], ctx["cell"]
    if not rec.get("refreshes"):
        return None
    _, hf = lm_common.program_config(cell)
    seq = int(cell.param("seq_len"))
    shape = lm_common.lm_shape(hf, seq)
    done = rec["docs"] * seq * flops.lm_extract_flops_per_token(shape)
    return 100.0 * done / (rec["window_s"] * ctx["peaks"]["bf16_flops"])
