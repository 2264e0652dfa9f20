"""Device idle time that a span of the program overlaps, as a share of
the traced window: ``{"reader": "idle_under_span", "span":
"train.report"}``. Idle is the complement of the union of the device's op
intervals, averaged over the device planes; the span's events come from
the host plane of the same profile, which shares the device planes' axis
(``host_plane.py``). The denominator is ``obs["trace"]["window_s"]``, the
one ``device_idle_share.*`` uses, so the two can be subtracted: what is
left is idle time under none of the program's spans."""

from benchmark import host_plane


def read(spec: dict, obs: dict):
    window = (obs.get("trace") or {}).get("window_s")
    if not window:
        return None
    idle = host_plane.idle_under(host_plane.of_run(), spec["span"])
    return None if idle is None else 100.0 * idle / window
