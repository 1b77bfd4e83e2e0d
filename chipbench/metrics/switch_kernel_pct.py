"""Share of the device's busy time in kernel (tpu_custom_call) ops.

Today these are the Pallas switch kernel's calls, one per tier and tick;
the kernel has no name of its own, so a second Pallas kernel on the path
would be counted here too."""


def read(ctx):
    tr = ctx["trace"]
    if tr["busy_s"] <= 0.0 or not tr["kernel_calls"]:
        return None
    return 100.0 * tr["kernel_s"] / tr["busy_s"]
