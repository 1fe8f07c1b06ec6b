"""Share of the rows the search gathered that were real rows: the real
rows of the distinct lists each unit of the window probes (the exact
probe of ``bench.counts.ivf_search``'s byte term), over the program's
counter ``ivf.gathered_rows`` (``nprobe`` lists of the store's gather
width per query, padding included)."""


def read(run):
    real = run["record"].get("real_rows")
    counters = (run.get("program") or {}).get("counters", {})
    gathered = counters.get("ivf.gathered_rows")
    if not real or not gathered:
        return None
    return 100.0 * real / gathered
