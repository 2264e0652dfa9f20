"""Device time of the programs whose name contains ``match``, as a share
of the traced window: ``{"reader": "module_share", "match":
"serve_prefill"}``. A program is one event of a device plane's ``XLA
Modules`` line, named after its jitted function (``jit_serve_prefill_tail
(<fingerprint>)``); the mean over device planes over
``obs["trace"]["window_s"]``, as ``device_idle_share.*`` has it."""

from benchmark import host_plane


def read(spec: dict, obs: dict):
    window = (obs.get("trace") or {}).get("window_s")
    if not window:
        return None
    s = host_plane.module_seconds(host_plane.of_run(), spec["match"])
    return None if s is None else 100.0 * s / window
