"""Idle share of the device in the traced window: 100 * (1 - busy /
window), busy being the union of the device-op intervals, averaged over
the chips used (``trace_reduce.py``)."""


def read(spec: dict, obs: dict):
    t = obs.get("trace")
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
