"""Model step: the planned forward's share of the chip's bf16 peak while
the device runs.  Useful FLOPs (the grouped, pruned convolutions the
mapping defines, per request image) of the batches launched inside the
traced window, over the device's busy seconds in that window, over the
peak in ``bench/peaks.json``."""


def read(run):
    t = run.trace
    images = run.window.traced_images
    if t is None or not images or t.busy_s <= 0:
        return None
    return (run.flops_per_image * images / t.busy_s
            / run.peaks["bf16_flops_per_s"])
