"""mfu: the whole round's share of the chips' peak, 100 x model FLOPs per
lane-round (the configuration's `flops_per_lane_round`) x the traced run's
lane-rounds per second / (chips x peak bf16 FLOP/s from peaks.json).  The
program's float32 matmuls run at default precision, one bf16 MXU pass each,
so the bf16 peak is the one they can reach."""


def read(ctx):
    if not ctx["lane_rounds"] or ctx["window_s"] <= 0:
        return None
    rate = ctx["lane_rounds"] / ctx["window_s"]
    return (100.0 * ctx["flops_per_lane_round"] * rate
            / (ctx["chips"] * ctx["peaks"]["bf16_flops"]))
