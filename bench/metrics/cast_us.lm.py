"""Device self time under the ``fed.cast`` scope in the language-model
sweep cells (the cast of the float32 master weights to the compute dtype,
and of the gradient back to float32, in every local step and the in-scan
eval), per trajectory-round, in microseconds: the traced window's device
ops summed by the innermost scope below the stages through the compiled
programs that ``run_sweep`` dispatches
(``repro.telemetry.scope_seconds``). Nothing where the program has no
such reading."""


def read(run):
    try:
        from repro.experiments.grid import sweep_hlo
        from repro.telemetry import scope_seconds
    except ImportError:
        return None
    if run.trace is None or run.units == 0:
        return None
    with run.workload._ctx():     # the precision is part of the program
        hlo = sweep_hlo(run.workload.spec)
    seconds = scope_seconds(run.trace.device_ops, hlo)
    return 1e6 * seconds.get("fed.cast", 0.0) / run.units
