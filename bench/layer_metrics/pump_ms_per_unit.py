"""Host time of the engine's scheduler per unit formed: the benchmark's
own span around each ``SearchEngine.pump`` call, summed over the window
and divided by the units the engine formed in it."""


def read(run):
    units = run["record"].get("units")
    if not units:
        return None
    return run["spans"].total_s("pump") * 1e3 / units
