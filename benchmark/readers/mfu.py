"""The whole step's share of the chip's bf16 peak: the FLOPs the window's
steps needed (``work.py``, from shapes and reported iterations) per window
second, over chips times peak."""


def read(entry: dict, context: dict):
    work, seconds = context["work"], context["window_s"]
    if not work or seconds <= 0:
        return None
    peak = context["peaks"]["bf16_flops_per_s"] * context["chips"]
    return 100.0 * work["flops"] / seconds / peak
