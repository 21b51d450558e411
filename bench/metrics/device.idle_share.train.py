"""Share of the traced training window in which no operation ran on the
device, %, averaged over the chips (bench/trace.py)."""
from bench import trace


def read(view):
    return trace.idle_share(view["trace"]) if view.get("train") else None
