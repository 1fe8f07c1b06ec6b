"""The two-pass Lloyd update's share of the device's busy time over the
traced window: the device seconds of innermost ops under the program's
``lloyd.update`` scope (argsort, gathers and ``sort_inverse_update``;
``bench.stages``) over the busy seconds."""


def read(run):
    tr = run["trace"]
    stage_s = (tr or {}).get("stage_s")
    if not stage_s or "lloyd.update" not in stage_s or not tr["busy_s"]:
        return None
    return 100.0 * stage_s["lloyd.update"] / tr["busy_s"]
