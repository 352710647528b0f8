"""The paged decode-attention kernel's share of its roofline: the least
time its calls' work needs at the chip's peaks (costs.decode_kernel,
valid context only), over the device time of its calls in the traced
decode steps."""


def read(run):
    t = run["trace"]
    k = t["kinds"]["decode"] if t else None
    if not k or not k["kernel_s"]:
        return None
    return 100.0 * k["kernel_min_s"] / k["kernel_s"]
