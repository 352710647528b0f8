"""The paged prefill-attention kernel's share of its roofline: the least
time its calls' work needs (costs.prefill_kernel), over the device time
of its calls in the traced prefill chunk steps."""


def read(run):
    t = run["trace"]
    k = t["kinds"]["prefill"] if t else None
    if not k or not k["kernel_s"]:
        return None
    return 100.0 * k["kernel_min_s"] / k["kernel_s"]
