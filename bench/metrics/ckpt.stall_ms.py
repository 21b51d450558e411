"""Stall of the window's checkpoint save, ms: the save's own time on the
training thread (its span: the device-to-host snapshot) plus the time by
which the steps that ran while its write was in flight exceeded the
window's median step."""
import statistics


def read(view):
    tr = view.get("train")
    if not tr:
        return None
    t0, t1 = view["window"]
    saves = [(a, b) for n, a, b in view["spans"]
             if n == "ckpt.save" and a >= t0 and b <= t1]
    if len(saves) != 1:
        return None
    a, b = saves[0]
    steps = list(tr["steps"].values())                   # (t_end, seconds)
    med = statistics.median(s for _, s in steps)
    end = b + tr["write_s"] + med
    excess = sum(s - med for t, s in steps if b < t <= end)
    return 1e3 * ((b - a) + excess)
