"""Model operations that the tokens of the step programs finished inside
the traced window need (costs.call_flops: the chip's share of the model,
no padding rows, no cached tokens), over the window's length at the
chip's peak."""


def read(run):
    t = run["trace"]
    if not t or not t["window_flops"]:
        return None
    return 100.0 * t["window_flops"] / (t["window_s"] * t["peak_flops"])
