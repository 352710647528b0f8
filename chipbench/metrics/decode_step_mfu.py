"""Model operations of the traced decode steps over their programs'
device time at the chip's peak: the whole decode step's share of peak,
which bounds what any kernel inside it can gain."""


def read(run):
    t = run["trace"]
    k = t["kinds"]["decode"] if t else None
    if not k or not k["module_s"] or not k["flops"]:
        return None
    return 100.0 * k["flops"] / (k["module_s"] * t["peak_flops"])
