"""Model FLOP utilization of the sweep cells: training FLOPs of the
trajectory-rounds completed in the window (forward, backward and SGD update
of every client, from the shapes), over the window, over the device's bf16
peak."""


def read(run):
    peak = run.peak.get("bf16_flops_per_s")
    if not peak or run.units == 0:
        return None
    flops = run.units * run.workload.train_flops_per_unit()
    return 100.0 * flops / run.window_s / peak
