"""Model FLOP utilisation of training, in %: operations the forward and
backward passes need per sequence (the layers on every position, causal
attention, the unembedding; recomputation not counted, as the
reference's ``train_flops_per_sequence`` counts them) times the steps
completed in the traced window, over the window at the chip's peak."""

PROGRAM = r"^jit_step$"


def read(rd):
    tr, t = rd.get("trace"), rd.get("train")
    if tr is None or t is None or tr.window_s <= 0:
        return None
    _, calls = tr.programs(PROGRAM)
    if not calls:
        return None
    ops = calls * t["batch"] * rd["counts"].train_flops_per_sequence(rd["dims"], t["seq"])
    return 100.0 * ops / (tr.window_s * rd["peak"]["bf16_flops_per_s"])
