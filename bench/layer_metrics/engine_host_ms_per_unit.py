"""Host time the engine spends forming and dispatching one search unit:
the program's ``engine.form`` and ``engine.dispatch`` spans, summed over
the window and divided by the program's counter ``ivf.units``. Nothing
is read where the program's record dropped spans (``obs.MAX_SPANS``):
the sum would then miss units the counter holds."""

SPANS = ("engine.form", "engine.dispatch")


def read(run):
    program = run.get("program") or {}
    units = program.get("counters", {}).get("ivf.units")
    if not units or program.get("dropped"):
        return None
    ns = sum(t1 - t0 for name, t0, t1, *_ in program.get("spans", ())
             if name in SPANS)
    return ns / 1e6 / units
