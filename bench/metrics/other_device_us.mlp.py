"""Device self time outside sampling, local training and aggregation, per
trajectory-round, in microseconds: the link draw, the postponed
broadcast, in-scan eval, the init stage, the eager batch and train-eval
ops between calls, copies, and any op no compiled program of ``run_sweep``
names (``repro.telemetry.stage_seconds``). Nothing where the program names
no stages."""

MEASURED_APART = ("fed.sample", "fed.local_train", "fed.aggregate")


def read(run):
    try:
        from repro.experiments.grid import sweep_hlo
        from repro.telemetry import stage_seconds
    except ImportError:
        return None
    if run.trace is None or run.units == 0:
        return None
    with run.workload._ctx():     # the precision is part of the program
        hlo = sweep_hlo(run.workload.spec)
    seconds = stage_seconds(run.trace.device_ops, hlo)
    rest = sum(v for k, v in seconds.items() if k not in MEASURED_APART)
    return 1e6 * rest / run.units
