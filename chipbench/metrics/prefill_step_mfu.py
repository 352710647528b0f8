"""Model operations of the traced prefill chunk steps over their
programs' device time at the chip's peak."""


def read(run):
    t = run["trace"]
    k = t["kinds"]["prefill"] if t else None
    if not k or not k["module_s"] or not k["flops"]:
        return None
    return 100.0 * k["flops"] / (k["module_s"] * t["peak_flops"])
