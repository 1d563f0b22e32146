"""Model FLOP utilization of the train steps in the window: the forward and
backward operations the steps' tokens need (``flops.lm_train_flops_per_token``,
recomputation not counted), over the window times the chip's bf16 peak."""
from chipbench import flops
from chipbench.drivers import lm_common


def read(ctx):
    rec, cell = ctx["record"], ctx["cell"]
    if not rec.get("steps"):
        return None
    _, hf = lm_common.program_config(cell)
    shape = lm_common.lm_shape(hf, int(cell.param("seq_len")))
    done = rec["tokens"] * flops.lm_train_flops_per_token(shape)
    return 100.0 * done / (rec["window_s"] * ctx["peaks"]["bf16_flops"])
