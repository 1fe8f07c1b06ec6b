"""The candidate gather's share of the device's busy time over the traced
window: the device seconds of innermost ops under the program's
``ivf.gather`` scope (``bench.stages``) over the busy seconds."""


def read(run):
    tr = run["trace"]
    stage_s = (tr or {}).get("stage_s")
    if not stage_s or "ivf.gather" not in stage_s or not tr["busy_s"]:
        return None
    return 100.0 * stage_s["ivf.gather"] / tr["busy_s"]
