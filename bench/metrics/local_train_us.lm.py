"""Device self time under the round engine's ``fed.local_train`` scope in
the language-model sweep cells (every client's local SGD steps under vmap:
the cast of the float32 masters to the compute dtype, forward, backward
and update), per trajectory-round, in microseconds: the traced window's
device ops summed by stage through the compiled programs that
``run_sweep`` dispatches (``repro.telemetry.stage_seconds``). Nothing
where the program names no stages."""


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
    return 1e6 * seconds.get("fed.local_train", 0.0) / run.units
