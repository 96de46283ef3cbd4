"""Device self time under the client model's ``lm.head`` scope in the
language-model sweep cells (the head tied to the embedding and the chunked
cross-entropy, forward and backward, in every local step, and the head of
the in-scan eval), per trajectory-round, in microseconds: the traced
window's device ops summed by the innermost scope below the stages
through the compiled programs that ``run_sweep`` dispatches
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
    return 1e6 * seconds.get("lm.head", 0.0) / run.units
