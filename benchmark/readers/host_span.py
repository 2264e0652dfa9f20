"""A span of the program, read from the host plane of the traced run's
own profile (``host_plane.py``): its events, or one of their attributes.

``{"reader": "host_span", "span": "train.report", "stat": "median_ms"}``
over the events' durations: ``median_ms``, ``count``, or ``sum_share``
(time in which such a span is open over the traced window
``obs["trace"]["window_s"]``). Or over an attribute the span carries:
``{"span": "serve.decode_block", "attr": "active", "over": "max_slots",
"stat": "weighted_mean", "weight": "n"}`` with ``stat`` ``p95``, ``mean``
or ``weighted_mean``; ``over`` divides each value by another attribute of
the same event. ``scale`` multiplies the result (100 for a share in %).

No host plane (no trace, or a profile without one): nothing to read. A
host plane that holds no such span (a program without spans): ``count``
is 0, every other stat reads nothing.
"""

import statistics

from benchmark import host_plane, latency


def read(spec: dict, obs: dict):
    evs = host_plane.spans(host_plane.of_run(), spec["span"])
    if evs is None:
        return None
    value = _stat(spec, evs, obs)
    return None if value is None else spec.get("scale", 1) * value


def _stat(spec: dict, evs: list, obs: dict):
    stat = spec["stat"]
    if stat == "count":
        return len(evs)
    if not evs:
        return None
    if "attr" not in spec:
        if stat == "median_ms":
            return statistics.median((e - s) / 1e6 for s, e, _, _ in evs)
        if stat == "sum_share":
            window = (obs.get("trace") or {}).get("window_s")
            if not window:
                return None
            open_ns = host_plane.total(
                host_plane.union([(s, e) for s, e, _, _ in evs]))
            return open_ns / 1e9 / window
        raise ValueError(f"host_span: unknown stat {stat!r}")
    rows = [st for _, _, _, st in evs if spec["attr"] in st]
    values = [st[spec["attr"]] / st[spec["over"]] if "over" in spec
              else st[spec["attr"]] for st in rows]
    if not values:
        return None
    if stat == "p95":
        return latency.percentile(values, 95)
    if stat == "mean":
        return statistics.fmean(values)
    if stat == "weighted_mean":
        weights = [st[spec["weight"]] for st in rows]
        return sum(v * w for v, w in zip(values, weights)) / sum(weights)
    raise ValueError(f"host_span: unknown stat {stat!r}")
