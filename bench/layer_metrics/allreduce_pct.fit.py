"""Share of the chips' busy time in the window's all-reduce ops, in a
fit cell whose points are sharded: the mean per-device seconds of the
ops named ``all-reduce*`` (the psum of the Lloyd statistics, and its
start and done halves where the compiler splits it) among the trace's
``device_ops``, over the mean busy seconds. None where no all-reduce op
is in the trace."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["busy_s"]:
        return None
    ops = [s for name, s in tr["device_ops"]
           if name.startswith("all-reduce")]
    if not ops:
        return None
    return 100.0 * sum(ops) / tr["busy_s"]
