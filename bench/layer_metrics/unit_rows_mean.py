"""Mean rows per search unit the engine formed in the window, from its own
counters (``queries_served`` over ``batches_formed``)."""


def read(run):
    rec = run["record"]
    if not rec.get("units"):
        return None
    return rec["queries"] / rec["units"]
