"""Device self time under the round engine's ``fed.aggregate`` scope in
the language-model sweep cells (the float32 server aggregation of the
clients' master weights and the broadcast back to them), per
trajectory-round, in microseconds: the traced window's device ops summed
by stage through the compiled programs that ``run_sweep`` dispatches
(``repro.telemetry.stage_seconds``). Nothing where the program names no
stages."""


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
    return 1e6 * seconds.get("fed.aggregate", 0.0) / run.units
