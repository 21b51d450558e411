"""Host time of the SDC guard per training step, ms: the spans around the
facade's ``verify_state``, ``scrub`` and ``check_metrics`` inside the
window, over the window's steps."""

GUARD = ("sdc.verify", "sdc.scrub", "sdc.sentinel")


def read(view):
    tr = view.get("train")
    if not tr or not tr["steps"]:
        return None
    t0, t1 = view["window"]
    busy = sum(b - a for n, a, b in view["spans"]
               if n in GUARD and a >= t0 and b <= t1)
    return 1e3 * busy / len(tr["steps"])
