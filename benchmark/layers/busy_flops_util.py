"""Analytic FLOPs of the traced passes over what the chip could have done
at its published bf16 peak in the time it was busy (device trace): MFU with
the idle time taken out, so what is left is the compiled program's own
inefficiency. Layer: Kernels."""

from benchmark import peaks


def read(run):
    c, t = run.counters, run.reduced_trace
    if t is None or "images_per_pass" not in c:
        return None
    ref = run.module("reference", run.config["reference"])
    flops = ref.train_flops_per_image(run.config["kwargs"]) \
        * c["images_per_pass"] * c["traced_passes"] / c["chips"]
    return 100.0 * flops / (
        t["busy_s"] * peaks.peak(c["device_kind"], "bf16_flops"))
