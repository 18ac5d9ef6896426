"""The fused selector kernel's device time as a share of the device's busy
time in the window: is the kernel or the forest fits the pace-setter?"""


def read(ctx):
    tr = ctx["trace"]
    kernel_s = (tr or {}).get("kernels", {}).get("select_step")
    if not kernel_s or not tr["busy_s"]:
        return None
    return 100.0 * kernel_s / tr["busy_s"]
