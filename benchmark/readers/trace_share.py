"""A trace quantity as a share of the traced window:
``{"reader": "trace_share", "key": "collective_exposed_s"}``. With
``"needs_chips": 4`` the metric exists only where that many chips ran."""


def read(spec: dict, obs: dict):
    t = obs.get("trace")
    if not t or not t.get("window_s") or t.get(spec["key"]) is None:
        return None
    if t.get("devices", 1) < spec.get("needs_chips", 1):
        return None
    return 100.0 * t[spec["key"]] / t["window_s"]
