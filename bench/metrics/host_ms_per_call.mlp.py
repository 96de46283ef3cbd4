"""Host time per ``run_sweep`` call in the window, in milliseconds: each
of the window's calls' ``sweep.run`` span less the ``sweep.wait`` spans
inside it (the host blocked on the device), averaged, from the program's
span ring (``repro.telemetry``). Nothing where the program records no
spans."""


def read(run):
    try:
        from repro import telemetry
    except ImportError:
        return None
    calls = run.units // run.workload.work_per_step
    spans = telemetry.records()
    runs = telemetry.last("sweep.run", calls, spans)
    if calls == 0 or len(runs) < calls:
        return None
    host = [r.seconds - sum(c.seconds for c in telemetry.children(r, spans)
                            if c.name == "sweep.wait") for r in runs]
    return 1e3 * sum(host) / len(host)
