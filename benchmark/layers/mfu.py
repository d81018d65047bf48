"""Model FLOP/s utilization: the analytic forward-and-backward FLOPs of an
image (the configuration's reference module, from flops.py) times images a
second a chip in the median pass, over the chip's published bf16 peak.
Recomputed operations are not credited. Layer: Step."""

from benchmark import peaks


def read(run):
    c = run.counters
    if "median_pass_s" not in c:
        return None
    ref = run.module("reference", run.config["reference"])
    rate = c["images_per_pass"] / c["median_pass_s"] / c["chips"]
    return 100.0 * ref.train_flops_per_image(run.config["kwargs"]) * rate \
        / peaks.peak(c["device_kind"], "bf16_flops")
